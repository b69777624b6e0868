"""`delta.fill_share` — delta plane: the fullest (block, part) delta
buffer's rows or tombstones, as a share of its capacity
(`tpu_delta_capacity_edges`), after the run's last apply (gauge
`tpu_delta_fill_ratio`).  The compaction watermark (0.75 by default) is
held against it: a run that passes it starts folding the plane into a new
base."""
from nebula_tpu.utils.stats import stats

NEEDS = ("tpu_delta_fill_ratio",)


def read(ctx):
    fill = stats().snapshot().get("tpu_delta_fill_ratio")
    return None if fill is None else 100.0 * fill
