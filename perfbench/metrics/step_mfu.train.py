"""Layer: model step.  The whole step's share of the chip's peak: tokens
per second of this run's untraced window x the benchmark's own FLOPs per
token / the peak of the benchmark's own table."""

import peaks
import registry


def read(ctx):
    cell, device = ctx["cell"], ctx["device"]
    flops = registry.load_module(cell.path(cell.cfg["flops"]))
    per_token = flops.flops_per_token(cell.cfg, cell.traffic)
    peak = peaks.peaks_for(device["kind"])["peak_flops_bf16"] * cell.chips
    return 100.0 * ctx["result"]["train_tokens_per_s"] * per_token / peak
