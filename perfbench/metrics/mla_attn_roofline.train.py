"""Layer: kernels.  The flash attention kernels' share of the chip's
bf16 peak: exact causal FLOPs a step (flops/joyai_train.py) over their
device time a step and the peak of the benchmark's own table."""

import peaks
import program_counters
import registry


def read(ctx):
    cell = ctx["cell"]
    ms = program_counters.kernel_ms(ctx, "flash_bhtd_")
    if ms is None:
        return None
    flops = registry.load_module(cell.path(cell.cfg["flops"]))
    peak = peaks.peaks_for(ctx["device"]["kind"])["peak_flops_bf16"]
    return 100.0 * flops.attention_flops_per_step(
        cell.cfg, cell.traffic) / (ms / 1e3 * peak * cell.chips)
