"""`device.idle_attributed_share` — device: of the traced slice's ns in
which NO device plane runs an `XLA Ops` event, the share in which some
program span other than a statement's `query:*` root is open on some
thread line: how much of the device's idle time the program's own spans
name.  A root alone explains nothing (0%); None where the trace holds no
program span at all (the parent in the proxy cells, which open no root
there) or the devices never idle."""
from benchmarks.lib import spans as S


def read(ctx):
    sl = S.slice_of(ctx)
    if sl is None or not sl[3]:
        return None
    t0, t1, busy, spans = sl
    idle_ns = (t1 - t0) - S.length_ns(busy)
    if idle_ns <= 0:
        return None
    named = S.clipped(((s, e) for name, _, s, e in spans if not name.startswith("query:")),
                      t0, t1)
    return 100.0 * (S.length_ns(named) - S.overlap_ns(named, busy)) / idle_ns
