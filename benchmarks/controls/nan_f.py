"""Control `nan_f`: every double column of the reference's answer not
read at all — NaN in each row, what a gather that reads the snapshot's
padding (the builders pad doubles with NaN) or a path that skips the
column's gathers would hand back.  The check refuses it row by row."""
import numpy as np

from benchmarks.lib.reply import Columns, columns_of


def broken(want):
    cols = columns_of(want)
    floats = [k for k in cols or () if cols[k].dtype.kind == "f" and cols[k].size]
    if not floats:
        return None
    return Columns({**cols, **{k: np.full_like(cols[k], np.nan) for k in floats}})
