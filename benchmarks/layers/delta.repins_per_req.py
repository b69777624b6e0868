"""`delta.repins_per_req` — delta plane: whole exports and pins of the
graph (`tpu_pins`) per request the driver sent, over the window's run.
Must read 0: a read-back served by a re-pin is a 20 s read (the census
breaking on a foreign writer, an overflow, an unsupported key).  Read only
on a program that has a delta apply to take instead (it keeps
`tpu_delta_apply_s`)."""
from benchmarks.lib.phases import kept

NEEDS = ("tpu_delta_apply_s.count",)


def read(ctx):
    n = len(ctx["records"])
    if not n or not kept("tpu_delta_apply_s.count"):
        return None
    return ctx["counter"]("tpu_pins") / n
