"""Layer: executor.  Seconds jax spent tracing jaxprs inside Executor
calls (the op loop runs here), over the whole process: a part of
`setup_s`."""

import program_spans


def read(ctx):
    phases = program_spans.compile_phases()
    return phases["trace_s"] if phases else None
