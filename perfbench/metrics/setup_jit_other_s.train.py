"""Layer: executor.  Seconds of the set-up misses' `dispatch` less jax's
trace, lower and backend-compile seconds of the same calls: what jax does
on a miss that no named phase covers."""

import setup_spans


def read(ctx):
    calls = setup_spans.setup_calls(ctx)
    return None if calls is None else setup_spans.jit_other_s(calls)
