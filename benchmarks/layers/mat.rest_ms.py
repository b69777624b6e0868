"""`mat.rest_ms` — device dispatch: what row assembly spends outside its
concatenations and decodes (`stmt_phase_us{phase=materialise}`: the SELF
time of `device:materialise` once `device:materialise.concat` and
`.decode` are its children: `eval_yield_column_np`, the delta plane's
re-sort `_delta_perms`, the wrapping into a `ColumnarDataSet`, the row
loop of a GO without yields), per statement.  Nothing on a program that
keeps no `mat_concat` phase (the parent, whose `materialise` is the whole
span)."""
from benchmarks.lib.spans import PHASE_N, phase_ms

NEEDS = (PHASE_N.format("mat_concat"),)


def read(ctx):
    return phase_ms(ctx, "materialise", needs=("mat_concat",))
