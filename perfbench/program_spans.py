"""Arithmetic over what the PROGRAM records about its own calls, for the
per-layer readers under metrics/: the `executor.run_steps` events of the
flight ring (paddle_tpu/monitor/flight.py: `t0`, `dur`, and `phases`, the
ordered [name, start offset, seconds] of the call's host phases), jax's
compile phases totalled inside Executor calls, and the kernel names in a
reduced trace.  Pure functions over lists and dicts;
perfbench/tests/test_program_spans.py checks them on hand-made events.

A trace directory is gone before a reader runs (report.reduce_trace), so
the spans come from the program's memory, in this same process.  A parent
commit that records none gives empty lists, and every function here then
returns None."""

CALL_KIND = "executor.run_steps"
PREPARE = ("feed", "key", "gather")
DISPATCH = ("dispatch",)


def traced_calls(ctx):
    """The flight events of the traced window's calls, oldest first: the
    last `traced.calls` of their kind in the ring ([] where the program
    keeps none)."""
    try:
        from paddle_tpu.monitor import flight
    except ImportError:
        return []
    n = ctx["result"]["traced"]["calls"]
    return [e for e in flight.default_recorder().events(kind=CALL_KIND)
            if e.get("phases")][-n:]


def compile_phases():
    """The program's totals of jax's compile phases, or None."""
    try:
        from paddle_tpu import monitor
    except ImportError:
        return None
    read = getattr(monitor, "compile_phases", None)
    return read() if read else None


def phase_ms(event, names):
    return 1e3 * sum(d for n, _, d in event["phases"] if n in names)


def after_a_gap(events):
    """The calls that follow a gap between two step programs: all but
    the first (one call alone stands for itself)."""
    return events[1:] or events


def mean_phase_ms(events, names):
    calls = after_a_gap(events)
    if not calls:
        return None
    return sum(phase_ms(e, names) for e in calls) / len(calls)


def outside_ms(events):
    """Mean time between the exit of one call and the entry of the next:
    the caller's."""
    pairs = list(zip(events, events[1:]))
    if not pairs:
        return None
    return 1e3 * sum(b["t0"] - (a["t0"] + a["dur"])
                     for a, b in pairs) / len(pairs)


def unexplained_ms(call_gap_ms, events):
    """What is left of the device's mean gap between two step programs
    once the host's time outside the calls, preparing and dispatching is
    taken off: the device->host return inside `fetch` and the launch
    latency.  The loop is closed, so the device is idle from the end of
    one program to the start of the next and all host time between them
    is exposed."""
    parts = [outside_ms(events), mean_phase_ms(events, PREPARE),
             mean_phase_ms(events, DISPATCH)]
    if not call_gap_ms or any(p is None for p in parts):
        return None
    return sum(call_gap_ms) / len(call_gap_ms) - sum(parts)


def kernel_ms_per_step(ops_s, tag, steps):
    """Milliseconds a step in Pallas kernels whose name holds `tag`
    (`_fwd` or `_bwd`: every kernel of the program carries exactly one).
    `ops_s` is the reduced trace's {short name: seconds}; a Pallas
    kernel's short name ends ` mosaic`."""
    if not steps:
        return None
    total = sum(t for name, t in ops_s.items()
                if name.endswith(" mosaic") and tag in name.split(" ")[0])
    return 1e3 * total / steps if total > 0 else None
