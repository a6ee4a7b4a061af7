"""Layer: executor.  Mean of the `dispatch` phase of the traced
`run_steps` calls that follow a gap: the compiled entry from call to
return (enqueued)."""

import program_spans


def read(ctx):
    return program_spans.mean_phase_ms(program_spans.traced_calls(ctx),
                                       program_spans.DISPATCH)
