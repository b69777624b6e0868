"""`dispatch.fetch_kept_share` — device dispatch (tpu/runtime.py `_fetch`):
of the bytes the fetches of the window's run brought to the host
(`tpu_fetch_bytes`: meta, the rungs that overflowed and discarded
speculation included), the share that is kept capture entries of the
columns the statements read (`tpu_fetch_bytes_kept`, i.e. kept count x
item size).  A program that lacks the counters (the parent) has nothing
to read.

It is bytes, not time: read it beside `dispatch.fetch_ms`.  It reads low
on tiny results, where the meta (a few hundred bytes a part) and the
floor under a slice or a piece outweigh tens of rows, and a statement
that captures nothing (BFS, `count(*)` programs) brings meta alone."""

# the counters without whose movement there is nothing to read
NEEDS = ("tpu_fetch_bytes",)


def read(ctx):
    fetched = ctx["counter"]("tpu_fetch_bytes")
    if not fetched:
        return None
    return 100.0 * ctx["counter"]("tpu_fetch_bytes_kept") / fetched
