"""`dispatch.mat_ms` — device dispatch: materialisation of the fetched
columns into result rows or hop frames (series `tpu_mat_s`,
tpu/runtime.py `traverse` / `traverse_hops`), per statement."""
from benchmarks.lib.phases import series_ms


def read(ctx):
    return series_ms(ctx, "tpu_mat_s")
