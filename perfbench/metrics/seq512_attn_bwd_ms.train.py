"""Layer: kernels.  Device time a step in the Pallas kernels whose name
holds `_bwd` (here `flash_bthd_bwd_dq` and `flash_bthd_bwd_dkv` in
multi-block walks): what `attn_bwd_ms.train` reads, in the cell its
`workloads` list does not name."""

import registry


def read(ctx):
    return registry.load_reader("attn_bwd_ms.train").read(ctx)
