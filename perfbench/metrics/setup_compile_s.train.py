"""Layer: executor.  Seconds in XLA's backend compile inside Executor
calls, over the whole process: a part of `setup_s`.  jax's event wraps
its persistent cache, so where the cache hit this is the time to load
the executable; standard error says which, with the hits and misses."""

import sys

import program_spans


def read(ctx):
    phases = program_spans.compile_phases()
    if not phases:
        return None
    print(f"compile_phases {phases}", file=sys.stderr)
    return phases["backend_s"]
