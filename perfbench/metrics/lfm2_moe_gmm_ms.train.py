"""Layer: kernels.  Device time a step in the grouped-matmul kernels of
the expert layers (`moe_gmm_fwd`, `_bwd_dx`, `_bwd_dw`): what
`moe_gmm_ms.train` reads, in the cell its `workloads` list does not
name."""

import registry


def read(ctx):
    return registry.load_reader("moe_gmm_ms.train").read(ctx)
