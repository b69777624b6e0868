"""`dispatch.device_ms` — device dispatch (tpu/runtime.py): host clock
around dispatch and block_until_ready, per statement.  Served cells read
d tpu_kernel_s / d num_queries; cells that enter at the runtime read the
mean TraverseStats.device_s.  A step time, not device busy time."""


def read(ctx):
    if ctx["served"]:
        n = ctx["counter"]("num_queries")
        return ctx["counter"]("tpu_kernel_s.sum") * 1e3 / n if n else None
    ts = ctx["tstats"]
    return 1e3 * sum(t.device_s for t in ts) / len(ts) if ts else None
