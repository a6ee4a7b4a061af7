"""Layer: executor.  Mean time between the exit of one traced
`run_steps` call and the entry of the next, from the program's flight
events: the caller's time (the benchmark's loop here, the reader in a
trainer)."""

import program_spans


def read(ctx):
    return program_spans.outside_ms(program_spans.traced_calls(ctx))
