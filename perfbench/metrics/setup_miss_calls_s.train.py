"""Layer: executor.  Seconds in the set-up calls that compiled (the
startup program's and the step's), from the first-calls record; prints
every set-up call on stderr (`setup_calls {..}`)."""

import program_spans
import setup_spans


def read(ctx):
    calls = setup_spans.setup_calls(ctx)
    if calls is None:
        return None
    setup_spans.describe(calls, program_spans.compile_phases())
    return setup_spans.miss_calls_s(calls)
