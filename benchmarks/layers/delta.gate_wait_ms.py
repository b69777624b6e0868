"""`delta.gate_wait_ms` — delta plane (tpu/runtime.py `_delta_update`,
span `tpu:delta_gate`): what the window's delta applies waited for the
dispatch gate's write side (series `tpu_delta_gate_wait_us`, one
observation a successful apply), per REQUEST the driver sent (a request
is a pair).  With one session nobody is in the way; with eight an apply
waits for the reads in flight and for the applies ahead of it.  A
program without the series has nothing to read."""
from benchmarks.lib.phases import kept

NEEDS = ("tpu_delta_gate_wait_us.count",)


def read(ctx):
    n = len(ctx["records"])
    if not n or not kept("tpu_delta_gate_wait_us.sum"):
        return None
    return ctx["counter"]("tpu_delta_gate_wait_us.sum") / 1e3 / n
