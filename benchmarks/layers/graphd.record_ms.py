"""`graphd.record_ms` — graphd: a statement's bookkeeping after its
executor (`stmt_phase_us{phase=record}`: span `graphd:record`,
exec/engine.py `_execute_parsed`: the latency series, the write epoch,
the result cache's put, the slow log, insights, the flight recorder), per
statement graphd counted.  `graphd.untraced_ms` fell by it in PR 39.
Nothing on a program without the phase (the parent)."""
from benchmarks.lib.spans import PHASE_N, phase_ms

NEEDS = (PHASE_N.format("record"),)


def read(ctx):
    return phase_ms(ctx, "record")
