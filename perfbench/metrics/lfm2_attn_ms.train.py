"""Layer: kernels.  Device time a step in the flash attention kernels of
the attention block (`flash_bhtd_fwd`, `_bwd_dq`, `_bwd_dkv`: causal, 32
query heads over 8 key/value heads of 64): what `mla_attn_ms.train`
reads, in the cell its `workloads` list does not name."""

import registry


def read(ctx):
    return registry.load_reader("mla_attn_ms.train").read(ctx)
