"""Layer: kernels.  Share of the device's busy time spent in Mosaic
(Pallas) custom calls."""


def read(ctx):
    t = ctx["trace"]
    if not t["mosaic_s"] > 0:
        return None
    return 100.0 * t["mosaic_s"] / t["busy_s"]
