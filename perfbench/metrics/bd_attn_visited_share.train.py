"""Layer: kernels.  The share of the [2L, 2L] score square's (query tile,
key tile) pairs that the masked flash forward walks visit: the program's
compile-time counts `attn_tiles_visited` / `attn_tiles_total`
(paddle_tpu/monitor/flight.py), summed over the attention sites traced.
25.05 % of the pairs are visible at L 2048, B 4; 512-row tiles reach
37.5 %.  A program that counts neither (the parent commit) gives None."""

import program_spans


def read(ctx):
    phases = program_spans.compile_phases() or {}
    total = phases.get("attn_tiles_total")
    if not total:
        return None
    return 100.0 * phases["attn_tiles_visited"] / total
