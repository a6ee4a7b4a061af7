"""Layer: kernels.  Device time a step in the Pallas kernels whose name
holds `_fwd`."""

import program_spans


def read(ctx):
    return program_spans.kernel_ms_per_step(
        ctx["trace"]["ops_s"], "_fwd", ctx["result"]["traced"]["steps"])
