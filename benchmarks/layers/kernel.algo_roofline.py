"""`kernel.algo_roofline` — kernels (algo/kernels.py): the bytes the
traced statements' algorithms have to move (lib/algo_bytes.py, from
shapes and the plain REFERENCE's counts, not from the program's counters:
a floor whatever implements a step) over what the chip could stream in the
device-busy seconds of the traced slice.  The bound is bytes.  Nothing to
read without a trace, or for a request whose operation has no such
profile."""
from benchmarks.lib import loader
from benchmarks.lib.algo_bytes import algo_bytes


def read(ctx):
    tr, traced = ctx["trace"], ctx["traced"]
    if not tr or not traced or not tr["busy_s"] or not ctx["peaks"]:
        return None
    need = 0
    for r in traced:
        req = ctx["requests"][r.idx]
        op = loader.module("reference/ops", req["template"]["op"])
        ran = getattr(op, "profile", lambda *_: None)(req["template"], req["start"])
        if not isinstance(ran, dict) or "algo" not in ran:
            return None
        need += algo_bytes(ran)
    return 100.0 * need / (tr["busy_s"] * ctx["peaks"]["hbm_bytes_per_s"])
