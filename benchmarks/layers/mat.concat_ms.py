"""`mat.concat_ms` — device dispatch: of row assembly, the fetched pieces
of each capture column joined into one owned column
(`stmt_phase_us{phase=mat_concat}`: spans `device:materialise.concat`,
one a column and block, around tpu/runtime.py `_cat_rows`: the parts
concatenated, a property column's 32-bit halves joined, `astype`), per
statement.  With `mat.decode_ms` and `mat.rest_ms` it sums to the
`device:materialise` spans, i.e. to `dispatch.mat_ms`.  Nothing on a
program without the phase (the parent)."""
from benchmarks.lib.spans import PHASE_N, phase_ms

NEEDS = (PHASE_N.format("mat_concat"),)


def read(ctx):
    return phase_ms(ctx, "mat_concat")
