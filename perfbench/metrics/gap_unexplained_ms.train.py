"""Layer: executor.  `executor_gap_ms.train` less the host's time outside
the calls, preparing and dispatching: what is left for the device->host
return inside `fetch` and the launch latency."""

import program_spans


def read(ctx):
    return program_spans.unexplained_ms(ctx["trace"]["call_gap_ms"],
                                        program_spans.traced_calls(ctx))
