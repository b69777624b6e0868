"""`compact.failures` — delta plane: compactions that raised over the
window's run (counter `tpu_compaction_failures`; the causes are in
`tpu_compaction_failures_by_cause{cause}`).  Must read 0.  Read only on
a program that counts them (it keeps the compaction's series): the
parent swallowed a failed compaction without a trace."""
from benchmarks.lib.phases import kept

NEEDS = ("tpu_compact_build_s.count",)


def read(ctx):
    if not kept("tpu_compact_build_s.count"):
        return None
    return ctx["counter"]("tpu_compaction_failures")
