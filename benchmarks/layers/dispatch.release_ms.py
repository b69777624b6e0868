"""`dispatch.release_ms` — device dispatch: giving a rung's device result
up after its fetch (`stmt_phase_us{phase=release}`: span `device:release`,
tpu/runtime.py `_escalate_locked`, the device result and the slices
`_fetch` cut of it let go), per statement.  It waits its turn for the GIL
under several sessions and was in no series and no span before PR 39.
Nothing on a program without the phase (the parent)."""
from benchmarks.lib.spans import PHASE_N, phase_ms

NEEDS = (PHASE_N.format("release"),)


def read(ctx):
    return phase_ms(ctx, "release")
