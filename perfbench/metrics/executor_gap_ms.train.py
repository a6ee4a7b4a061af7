"""Layer: executor.  Mean idle time on the device between the end of one
`run_steps` executable and the start of the next, over the traced calls."""


def read(ctx):
    gaps = ctx["trace"]["call_gap_ms"]
    return sum(gaps) / len(gaps) if gaps else None
