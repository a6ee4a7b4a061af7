"""Layer: model step.  Device busy time in the traced window per step."""


def read(ctx):
    steps = ctx["result"]["traced"]["steps"]
    return 1e3 * ctx["trace"]["busy_s"] / steps if steps else None
