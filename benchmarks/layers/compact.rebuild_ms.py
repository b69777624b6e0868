"""`compact.rebuild_ms` — delta plane (tpu/runtime.py `_compact`, span
`tpu:compact_build`): what building the fresh base took, OFF the gate,
per compaction that ended inside the window's run (series
`tpu_compact_build_s`: the copy of the plane's host mirror, the fold of
base rows minus tombstones plus delta rows, the HBM budget check), with
eight sessions sharing the host.  Nothing to read when no compaction
ended in the window (the run missed its mechanism, and is seen by that)
or on a program without the series (the parent)."""

NEEDS = ("tpu_compact_build_s.count",)


def read(ctx):
    n = ctx["counter"]("tpu_compact_build_s.count")
    if not n or not ctx["counter"]("tpu_compactions"):
        return None
    return ctx["counter"]("tpu_compact_build_s.sum") * 1e3 / n
