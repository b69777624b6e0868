"""`mat.pooled_rows_share` — device dispatch: of the rows a statement's
row assembly put together (`tpu_mat_rows`, kept rows a block, summed),
the share whose pieces were joined SIDE BY SIDE by the assembly's worker
threads (`tpu_mat_pooled_rows`: tpu/runtime.py `_cat_side_by_side`, one
task a fetched piece) and not one after another on the statement's own
thread; sums over the window's run.  The code hands a block's pieces
over where its kept row count is at or over `POOL_MIN_ROWS` and the rows
keep their fetched order, so this says how much of a cell's traffic is
large by that measure: 100 in the one-chip proxy cell, 0 in a served
cell of some thousand rows a statement.  Read it beside `mat.concat_ms`.
Nothing to read on a program without the series (the parent) or in a
window that assembled no row."""

NEEDS = ("tpu_mat_rows.sum",)


def read(ctx):
    rows = ctx["counter"]("tpu_mat_rows.sum")
    if not rows:
        return None
    return 100.0 * ctx["counter"]("tpu_mat_pooled_rows.sum") / rows
