"""Layer: kernels.  Device time a step in the flash attention kernels of
the latent-attention blocks (`flash_bhtd_fwd`, `_bwd_dq`, `_bwd_dkv`:
d_qk 192, d_v 128)."""

import program_counters


def read(ctx):
    return program_counters.kernel_ms(ctx, "flash_bhtd_")
