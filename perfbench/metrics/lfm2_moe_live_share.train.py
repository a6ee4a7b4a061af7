"""Layer: router.  The live share of the expert layers' walk: the pairs
this chip's experts computed over the rows the walk visited for them, both
device counters of the program (`moe_local_pairs` / `moe_rows_walked`,
whole chunks of ops/llm_ops.py `chunk_rows`), over the traced calls.  100
would be chunks with no dead row; a second, almost empty trip halves it.
A program that publishes neither (the parent commit) gives None."""

import program_counters


def read(ctx):
    pairs = program_counters.traced_counter(ctx, "moe_local_pairs")
    walked = program_counters.traced_counter(ctx, "moe_rows_walked")
    if pairs is None or not walked:
        return None
    return 100.0 * pairs / walked
