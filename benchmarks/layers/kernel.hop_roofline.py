"""`kernel.hop_roofline` — kernels (tpu/hop.py): the bytes the traced
statements' hops need (lib/arith.py hop_bytes, from shapes only) over
what the chip could stream in the device-busy seconds of the traced
slice.  The bound is bytes: the hops do no FLOPs worth counting.  Over
100% means the byte model is wrong, not that the kernel is fast.  Needs
TraverseStats, so only cells that enter at TpuRuntime.traverse."""
from benchmarks.lib.arith import hop_bytes


def read(ctx):
    tr, traced = ctx["trace"], [r for r in ctx["traced"] if r.stats is not None]
    if not tr or not traced or not tr["busy_s"] or not ctx["peaks"]:
        return None
    need = 0
    for r in traced:
        t = ctx["requests"][r.idx]["template"]
        # `d` is the neighbour id, which every hop reads anyway
        props = [c for c in t["cols"] if c != "d"] + (["w"] if t.get("w_gt") is not None else [])
        need += hop_bytes(r.stats.hop_edges, r.stats.frontier_sizes, props,
                          ctx["schema"]["edges"][t["over"][0]])
    return 100.0 * need / (tr["busy_s"] * ctx["peaks"]["hbm_bytes_per_s"])
