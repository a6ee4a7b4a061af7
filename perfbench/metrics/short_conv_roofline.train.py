"""Layer: kernels.  The short-convolution kernels' share of their
roofline, which is HBM bytes (no matrix product): the bytes a step that
`short_conv_bytes_per_step` (flops/lfm2_train.py) counts, operands and
results once a pass, over the kernels' device time a step and the HBM peak
of the benchmark's own table."""

import peaks
import program_counters
import registry


def read(ctx):
    cell = ctx["cell"]
    ms = program_counters.kernel_ms(ctx, "short_conv_")
    if ms is None:
        return None
    flops = registry.load_module(cell.path(cell.cfg["flops"]))
    peak = peaks.peaks_for(ctx["device"]["kind"])["peak_hbm_bytes_per_s"]
    return 100.0 * flops.short_conv_bytes_per_step(
        cell.cfg, cell.traffic) / (ms / 1e3 * peak * cell.chips)
