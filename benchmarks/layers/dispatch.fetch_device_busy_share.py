"""`dispatch.fetch_device_busy_share` — device dispatch: of the ns inside
the traced slice in which a `device:fetch*` span is open on some thread
line (tpu/runtime.py `_fetch`: the transfers and `device:fetch.rows`),
the share in which some device plane runs an operation.  With ONE session
that is the fetch's own slice programs on the device; with two, what it
reads above that is a fetch waiting behind the other session's hop
program.  None where the slice holds no such span (the parent in the
proxy cells)."""
from benchmarks.lib import spans as S


def read(ctx):
    sl = S.slice_of(ctx)
    if sl is None:
        return None
    t0, t1, busy, spans = sl
    fetch = S.clipped(((s, e) for name, _, s, e in spans if name.startswith("device:fetch")),
                      t0, t1)
    total = S.length_ns(fetch)
    return 100.0 * S.overlap_ns(fetch, busy) / total if total else None
