"""Layer: device.  The run's `memory_peak_bytes`: `memory_stats()`'s
`peak_bytes_in_use` plus `peak_bytes_reserved` on the fullest chip, read
after the window and before the reference runs (drivers/train.py,
`device_peak_parts`)."""


def read(ctx):
    peak = ctx["device"]["memory_peak_bytes"]
    return peak / 2 ** 30 if peak else None
