"""Layer: executor.  Seconds from jaxpr to MLIR module inside Executor
calls, over the whole process: a part of `setup_s`."""

import program_spans


def read(ctx):
    phases = program_spans.compile_phases()
    return phases["lower_s"] if phases else None
