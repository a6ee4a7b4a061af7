"""Layer: kernels.  Device time a step in the Pallas kernels whose name
holds `_bwd`."""

import program_spans


def read(ctx):
    return program_spans.kernel_ms_per_step(
        ctx["trace"]["ops_s"], "_bwd", ctx["result"]["traced"]["steps"])
