"""`kernel.hop_roofline.mesh` — kernels (tpu/hop.py, the sharded
program): the bytes the traced statements' hops need (`kernel.hop_roofline`'s
count: lib/arith.py `hop_bytes`, from shapes only) plus the bytes their
frontier exchanges move (`TraverseStats.exchange_bytes`), over what ALL
the cell's chips could stream from HBM in the device-busy seconds of the
traced slice (`busy_s` is the mean over the chips, so the divisor is
busy_s x chips x one chip's bandwidth).  The bound is bytes.  Over 100%
means the count is wrong, not that the kernel is fast."""
from benchmarks.lib import loader


def read(ctx):
    # the one-chip reader's share: the hops' bytes over busy_s x ONE chip's bandwidth
    hops = loader.module("layers", "kernel.hop_roofline").read(ctx)
    if hops is None:
        return None
    moved = sum(int(getattr(r.stats, "exchange_bytes", 0)) for r in ctx["traced"]
                if r.stats is not None)
    one_chip = ctx["trace"]["busy_s"] * ctx["peaks"]["hbm_bytes_per_s"]
    return (hops + 100.0 * moved / one_chip) / ctx["chips"]
