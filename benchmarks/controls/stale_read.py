"""Control `stale_read`: the reference's answer without the source's
newest acknowledged write — what a snapshot that missed the write hands
back.  In `write_read` the written `w` is a running number above every
generated one, so on any table with a `w` column the newest write is the
row of the largest `w`, and that row is dropped.  The check refuses it by
`rows_mismatched` (limit 0)."""
import numpy as np

from benchmarks.lib.reply import Columns, columns_of


def broken(want):
    cols = columns_of(want)
    if cols is None or "w" not in cols or cols["w"].size == 0:
        return None
    keep = np.ones(cols["w"].size, bool)
    keep[int(np.argmax(cols["w"]))] = False
    return Columns({k: v[keep] for k, v in cols.items()})
