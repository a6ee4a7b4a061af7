"""Layer: kernels.  Device time a step in the grouped-matmul kernels of
the expert layers (`moe_gmm_fwd`, `_bwd_dx`, `_bwd_dw`)."""

import program_counters


def read(ctx):
    return program_counters.kernel_ms(ctx, "moe_gmm_")
