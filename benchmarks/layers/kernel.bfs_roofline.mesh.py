"""`kernel.bfs_roofline.mesh` — kernels (tpu/bfs.py `build_bfs_fn` over
algo/frontier.py `sharded_level_step`, the `shard_map` program): the bytes
a level-synchronous top-down BFS has to move for the traced statements
(`kernel.bfs_roofline`'s count: lib/bfs_bytes.py over the plain
REFERENCE's level profile, so the same work whatever implements a level,
a bottom-up one included) plus what its owners have to exchange after
every level (lib/bfs_mesh_bytes.py, from shapes alone), over what ALL the
cell's chips could stream from HBM in the device-busy seconds of the
traced slice (`busy_s` is the mean over the chips' planes).  The bound is
bytes.  Nothing to read without a trace, or for a request the reference's
BFS never ran from."""
from benchmarks.lib import loader
from benchmarks.lib.bfs_bytes import bfs_bytes
from benchmarks.lib.bfs_mesh_bytes import bfs_mesh_bytes


def read(ctx):
    tr, traced = ctx["trace"], ctx["traced"]
    if not tr or not traced or not tr["busy_s"] or not ctx["peaks"]:
        return None
    chips, need = int(ctx["chips"]), 0
    for r in traced:
        req = ctx["requests"][r.idx]
        op = loader.module("reference/ops", req["template"]["op"])
        ran = getattr(op, "profile", lambda *_: None)(req["template"], req["start"])
        if ran is None:
            return None
        expanded, vertices = ran
        need += bfs_bytes(expanded, vertices) + bfs_mesh_bytes(
            len(expanded), chips, -(-vertices // chips))
    return 100.0 * need / (tr["busy_s"] * chips * ctx["peaks"]["hbm_bytes_per_s"])
