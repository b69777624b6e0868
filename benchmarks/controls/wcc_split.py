"""Control `wcc_split`: one vertex moved out of its component into a
component of its own, under its own vid, which is a sound label for a
component of one: only a comparison of the PARTITION sees it (in a table
without a `component` column, or whose components are all of one vertex,
one entry of its first integer column moved by one).  The check refuses it
by `rows_mismatched` (limit 0)."""
import numpy as np

from benchmarks.lib.reply import Columns, columns_of


def broken(want):
    cols = columns_of(want)
    ints = sorted(c for c in cols or () if cols[c].dtype.kind in "iu" and cols[c].size)
    if not ints:
        return None
    if "component" in ints and "vid" in ints:
        inside = np.flatnonzero(cols["component"] != cols["vid"])
        if inside.size:
            changed = cols["component"].copy()
            at = inside[inside.size // 2]
            changed[at] = cols["vid"][at]
            return Columns({**cols, "component": changed})
    k = ints[0]
    changed = cols[k].copy()
    changed[changed.size // 2] += 1
    return Columns({**cols, k: changed})
