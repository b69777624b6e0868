"""`device.idle_share` — device: 1 - (union of device-operation
intervals / traced slice), from the profiler's trace."""


def read(ctx):
    return ctx["trace"]["idle_share"] if ctx["trace"] else None
