"""`dispatch.hostside_ms` — device dispatch, its host side: mean
TraverseStats.put_s + fetch_s + mat_s per statement (seed put, result
fetch, row materialisation).  Only where the runtime hands its
TraverseStats back, i.e. cells that enter at TpuRuntime.traverse."""


def read(ctx):
    ts = ctx["tstats"]
    if not ts:
        return None
    return 1e3 * sum(t.put_s + t.fetch_s + t.mat_s for t in ts) / len(ts)
