"""Layer: kernels.  Device time a step in the flash attention kernels of
the block-diffusion blocks (`flash_bhtd_fwd`, `_bwd_dq`, `_bwd_dkv`: 32
query heads over 4 key/value heads of 128, masked by position): what
`mla_attn_ms.train` reads, in this cell."""

import registry


def read(ctx):
    return registry.load_reader("mla_attn_ms.train").read(ctx)
