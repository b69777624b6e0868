"""Control `bad_row`: one integer of one row of the reference's answer
altered.  The check refuses it by `rows_mismatched` (limit 0)."""
from benchmarks.lib.reply import Columns, columns_of


def broken(want):
    cols = columns_of(want)
    if cols is None or next(iter(cols.values())).size == 0:
        return None
    k = sorted(c for c in cols if cols[c].dtype.kind != "f")[0]
    changed = cols[k].copy()
    changed[changed.size // 2] += 1
    return Columns({**cols, k: changed})
