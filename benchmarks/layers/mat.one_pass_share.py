"""`mat.one_pass_share` — device dispatch: of the numeric property
columns a statement's row assembly decoded (`tpu_mat_numeric_cols`: an
integer or a float kind through graphstore/csr.py
`decode_prop_column_np`), the share whose one question, does any slot
hold the kind's NULL sentinel?, was answered by the pass that assembled
the column from its fetched pieces (`tpu_mat_one_pass_cols`:
tpu/runtime.py `_join_halves`, the join of a device-gathered column's
32-bit halves), so that the decode neither copied nor scanned it; sums
over the window's run.  A column gathered on the host at the captured
`eidx` has no such pass and counts against it.  Read it beside
`mat.decode_ms`, which is what it takes down.  Nothing to read on a
program without the series (the parent) or in a window that decoded no
numeric column."""

NEEDS = ("tpu_mat_numeric_cols.sum",)


def read(ctx):
    cols = ctx["counter"]("tpu_mat_numeric_cols.sum")
    if not cols:
        return None
    return 100.0 * ctx["counter"]("tpu_mat_one_pass_cols.sum") / cols
