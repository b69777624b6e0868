"""`mesh.busy_skew` — mesh exchange: the busiest chip's busy seconds over
the chips' mean, inside the traced slice (busy = the union of a chip's
`XLA Ops` intervals, as `device.idle_share` takes it).  1.0 is even.  The
fullest part sets every part's trip count and every part waits at the
exchange, so a skew shows as collective seconds on the idler chips."""
from benchmarks.lib import trace as T


def read(ctx):
    events = ctx["events"]
    bounds = T.window(events) if events else None
    if bounds is None or not events["devices"]:
        return None
    t0, t1 = bounds
    busy = [sum(e - s for s, e in T.union(
        [(max(s, t0), min(e, t1)) for _, s, e in ops if e > t0 and s < t1]))
        for ops in events["devices"].values()]
    if not sum(busy):
        return None
    return max(busy) * len(busy) / sum(busy)
