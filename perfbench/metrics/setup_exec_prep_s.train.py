"""Layer: executor.  Seconds of the set-up misses' `compile` phase: the
executor's own verify, state split, step closure and `jax.jit`."""

import setup_spans


def read(ctx):
    calls = setup_spans.setup_calls(ctx)
    return None if calls is None else setup_spans.exec_prep_s(calls)
