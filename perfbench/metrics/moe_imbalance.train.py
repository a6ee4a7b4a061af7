"""Layer: router.  The largest held expert's load over the mean load of
the held experts, all expert layers together, a step (the program's
`moe_max_over_mean` device counter): 1 is an even split."""

import program_counters


def read(ctx):
    return program_counters.traced_counter(ctx, "moe_max_over_mean")
