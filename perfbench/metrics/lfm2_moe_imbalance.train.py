"""Layer: router.  The largest held expert's load over the mean load of
the held experts, all layers together, a step (the program's
`moe_max_over_mean` device counter), as `moe_imbalance.train` reads it."""

import registry


def read(ctx):
    return registry.load_reader("moe_imbalance.train").read(ctx)
