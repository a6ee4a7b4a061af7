"""Layer: kernels.  The masked flash attention kernels' share of the
chip's bf16 peak, read as `mla_attn_roofline.train` reads J's: this
cell's `attention_flops_per_step` (flops/sdar_train.py) counts the exact
FLOPs of the VISIBLE (query, key) pairs a step, over the kernels' device
time a step and the peak of the benchmark's own table.  Whole tiles on the
block diagonals make it read low by design."""

import registry


def read(ctx):
    return registry.load_reader("mla_attn_roofline.train").read(ctx)
