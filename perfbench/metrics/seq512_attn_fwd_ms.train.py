"""Layer: kernels.  Device time a step in the Pallas kernels whose name
holds `_fwd` (here `flash_bthd_fwd` in multi-block walks): what
`attn_fwd_ms.train` reads, in the cell its `workloads` list does not
name."""

import registry


def read(ctx):
    return registry.load_reader("attn_fwd_ms.train").read(ctx)
