"""`kernel.bfs_roofline` — kernels (tpu/bfs.py over algo/frontier.py):
the bytes a level-synchronous top-down BFS has to move for the traced
statements (lib/bfs_bytes.py, from the plain REFERENCE's level profile and
not from the program's counters, so that it reads the same work whatever
implements a level) over what the chip could stream in the device-busy
seconds of the traced slice.  The bound is bytes.  A bottom-up level may
read fewer ids than the count, so this is the share of the top-down
roofline.  Nothing to read without a trace, or for a request the
reference's BFS never ran from."""
from benchmarks.lib import loader
from benchmarks.lib.bfs_bytes import bfs_bytes


def read(ctx):
    tr, traced = ctx["trace"], ctx["traced"]
    if not tr or not traced or not tr["busy_s"] or not ctx["peaks"]:
        return None
    need = 0
    for r in traced:
        req = ctx["requests"][r.idx]
        op = loader.module("reference/ops", req["template"]["op"])
        ran = getattr(op, "profile", lambda *_: None)(req["template"], req["start"])
        if ran is None:
            return None
        need += bfs_bytes(*ran)
    return 100.0 * need / (tr["busy_s"] * ctx["peaks"]["hbm_bytes_per_s"])
