"""Layer: kernels.  The grouped-matmul kernels' share of their roofline:
the larger of FLOPs / peak and bytes / HBM bandwidth, both from the
`moe_local_pairs` counter (pairs this chip computed a step) and the held
experts' weights (flops/joyai_train.py), over the kernels' device time a
step.  At some hundred rows an expert the bound is the weights'
bandwidth."""

import peaks
import program_counters
import registry


def read(ctx):
    cell = ctx["cell"]
    ms = program_counters.kernel_ms(ctx, "moe_gmm_")
    pairs = program_counters.traced_counter(ctx, "moe_local_pairs")
    if ms is None or pairs is None:
        return None
    flops = registry.load_module(cell.path(cell.cfg["flops"]))
    peak = peaks.peaks_for(ctx["device"]["kind"])
    bound_s = max(
        flops.gmm_flops_per_step(cell.cfg, pairs) / peak["peak_flops_bf16"],
        flops.gmm_bytes_per_step(cell.cfg, pairs)
        / peak["peak_hbm_bytes_per_s"])
    return 100.0 * bound_s / (ms / 1e3 * cell.chips)
