"""Layer: kernels.  The grouped-matmul kernels' share of their roofline,
as `moe_gmm_roofline.train` reads it, with this cell's
`gmm_flops_per_step` and `gmm_bytes_per_step` (flops/lfm2_train.py): at a
thousand rows an expert the multiply-adds are the bound."""

import registry


def read(ctx):
    return registry.load_reader("moe_gmm_roofline.train").read(ctx)
