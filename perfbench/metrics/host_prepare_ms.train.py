"""Layer: executor.  Mean of the `feed` + `key` + `gather` phases of the
traced `run_steps` calls that follow a gap: the feed's host->device
copy, the cache key, the state out of the scope and the step key."""

import program_spans


def read(ctx):
    return program_spans.mean_phase_ms(program_spans.traced_calls(ctx),
                                       program_spans.PREPARE)
