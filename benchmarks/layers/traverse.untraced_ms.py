"""`traverse.untraced_ms` — device dispatch: the self time of a device
statement's own root (`stmt_phase_us{phase=other}` of the `query:tpu.*`
root that tpu/runtime.py `_on_live_snapshot` opens where a statement
enters at `TpuRuntime.traverse` with no trace active), per statement the
driver sent: what no span below the entry explains.  The proxy cells'
`graphd.untraced_ms`.  Nothing on a program that opens no such root (the
parent)."""
from benchmarks.lib.spans import PHASE_N, phase_ms

NEEDS = (PHASE_N.format("other"),)


def read(ctx):
    return phase_ms(ctx, "other")
