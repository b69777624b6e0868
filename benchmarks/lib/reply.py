"""What a session hands back for one request, whatever the builder, and
the same shape for an answer a control puts in the program's place."""
from __future__ import annotations

import numpy as np


class Reply:
    __slots__ = ("n_rows", "data", "error", "stats")

    def __init__(self, n_rows=0, data=None, error=None, stats=None):
        self.n_rows, self.data, self.error, self.stats = n_rows, data, error, stats

    def column(self, name):
        """One result column as a numpy array, in the type it arrived."""
        arr = getattr(self.data, "column_array", lambda _n: None)(name)
        if arr is None:
            arr = np.asarray(self.data.column(name))
        return np.asarray(arr)

    def rows(self):
        return self.data.rows


class Columns:
    """A reply made of plain columns: what a control (controls/<name>.py)
    returns, the reference's answer broken in one named way."""

    def __init__(self, cols):
        self.cols = cols

    def column(self, name):
        return self.cols[name]


def columns_of(want):
    """The reference's answer as {column: array} where it is a table, else
    None (paths, subgraphs and counts have no column to break)."""
    return want if isinstance(want, dict) and want and \
        all(isinstance(v, np.ndarray) for v in want.values()) else None
