"""`delta.put_ms` — delta plane: seconds of the applies' device puts (the
changed delta blocks, series `tpu_delta_put_s`, inside `device:delta_put`),
per request the driver sent.  Nothing to read on a program without the
series."""
from benchmarks.lib.phases import kept

NEEDS = ("tpu_delta_put_s.count",)


def read(ctx):
    n = len(ctx["records"])
    if not n or not kept("tpu_delta_put_s.sum"):
        return None
    return ctx["counter"]("tpu_delta_put_s.sum") * 1e3 / n
