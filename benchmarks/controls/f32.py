"""Control `f32`: every double column of the reference's answer computed
or shipped through float32 — the step a later PR would be tempted by
(half the bytes per edge).  The check refuses it by `float_rel_gap`."""
import numpy as np

from benchmarks.lib.reply import Columns, columns_of


def broken(want):
    cols = columns_of(want)
    floats = [k for k in cols or () if cols[k].dtype.kind == "f" and cols[k].size]
    if not floats:
        return None
    return Columns({**cols, **{k: cols[k].astype(np.float32).astype(np.float64)
                               for k in floats}})
