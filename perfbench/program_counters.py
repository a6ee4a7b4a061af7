"""What the per-layer readers of the expert layer share: the program's
device counters (`counters` of the `executor.run_steps` flight events,
paddle_tpu/monitor/flight.py `device_counter`) over the traced calls.  A
program that publishes none (the parent commit) gives None."""

import program_spans


def traced_counter(ctx, name):
    """Mean over the traced calls of a device counter (itself the mean
    over a call's steps), or None."""
    values = [e["counters"][name] for e in program_spans.traced_calls(ctx)
              if name in e.get("counters", {})]
    return sum(values) / len(values) if values else None


def kernel_ms(ctx, prefix):
    """Device milliseconds a step in the Pallas kernels named `prefix`*."""
    return program_spans.kernel_ms_per_step(
        ctx["trace"]["ops_s"], prefix, ctx["result"]["traced"]["steps"])
