"""`delta.apply_ms` — delta plane (tpu/runtime.py `_try_delta_update`):
seconds the window's reads spent folding acknowledged writes into the
resident delta plane (series `tpu_delta_apply_s`: entry to return, the
census fan-outs, the gate wait, the key re-reads and the put inside),
per REQUEST the driver sent (a request is a pair: graphd counts two
queries).  A program without the series (the parent) has nothing to
read."""
from benchmarks.lib.phases import kept

NEEDS = ("tpu_delta_apply_s.count",)


def read(ctx):
    n = len(ctx["records"])
    if not n or not kept("tpu_delta_apply_s.sum"):
        return None
    return ctx["counter"]("tpu_delta_apply_s.sum") * 1e3 / n
