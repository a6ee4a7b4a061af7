"""Layer: executor.  Seconds in the set-up calls that hit (the checked
calls, the second start-up run, the warm call), from the first-calls
record."""

import setup_spans


def read(ctx):
    calls = setup_spans.setup_calls(ctx)
    return None if calls is None else setup_spans.hit_calls_s(calls)
