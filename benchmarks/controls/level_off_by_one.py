"""Control `level_off_by_one`: one reached vertex a level further out than
the reference has it (in a table without a `level` column, one
non-negative entry of its first integer column moved by one).  The check
refuses it by `rows_mismatched` (limit 0)."""
import numpy as np

from benchmarks.lib.reply import Columns, columns_of


def reached(cols):
    """-> (the column that holds levels, positions of its entries of 0
    or more), or None where the table has no integer to break."""
    ints = sorted(c for c in cols if cols[c].dtype.kind in "iu")
    if not ints:
        return None
    k = "level" if "level" in ints else ints[0]
    at = np.flatnonzero(cols[k] >= 0)
    return (k, at) if at.size else None


def broken(want):
    cols = columns_of(want)
    found = cols and reached(cols)
    if not found:
        return None
    k, at = found
    changed = cols[k].copy()
    changed[at[at.size // 2]] += 1
    return Columns({**cols, k: changed})
