"""`mat.decode_ms` — device dispatch: of row assembly, the decodes
(`stmt_phase_us{phase=mat_decode}`: spans `device:materialise.decode`,
one a column and block, in tpu/runtime.py `_block_columns`: the
dense-to-vid gather of a `src` / `dst` column where dense ids are not the
vids, a host column gathered at the captured `eidx`, and
`decode_prop_column*`), per statement.  Nothing on a program without the
phase (the parent)."""
from benchmarks.lib.spans import PHASE_N, phase_ms

NEEDS = (PHASE_N.format("mat_decode"),)


def read(ctx):
    return phase_ms(ctx, "mat_decode")
