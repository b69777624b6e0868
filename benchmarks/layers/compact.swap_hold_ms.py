"""`compact.swap_hold_ms` — delta plane (tpu/runtime.py `_compact`,
span `tpu:compact_swap`): how long a compaction held the gate's write
side, per swap inside the window's run (series `tpu_compact_swap_s`:
the writes applied during the build carried over, the old buffers given
up, the new base pinned, its plane armed).  Every reader waits it out.
Nothing to read when no swap landed in the window or on a program
without the series (the parent)."""

NEEDS = ("tpu_compact_swap_s.count",)


def read(ctx):
    n = ctx["counter"]("tpu_compact_swap_s.count")
    if not n:
        return None
    return ctx["counter"]("tpu_compact_swap_s.sum") * 1e3 / n
