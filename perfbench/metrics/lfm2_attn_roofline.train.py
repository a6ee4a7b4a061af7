"""Layer: kernels.  The causal flash attention kernels' share of the
chip's bf16 peak, read as `mla_attn_roofline.train` reads J's: this
cell's `attention_flops_per_step` (flops/lfm2_train.py) counts the exact
causal FLOPs a step, over the kernels' device time a step and the peak of
the benchmark's own table.  Heads of 64 fill half of the MXU's width."""

import registry


def read(ctx):
    return registry.load_reader("mla_attn_roofline.train").read(ctx)
