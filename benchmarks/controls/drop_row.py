"""Control `drop_row`: the last row of the reference's answer missing.
The check refuses it by `rows_mismatched` (limit 0)."""
from benchmarks.lib.reply import Columns, columns_of


def broken(want):
    cols = columns_of(want)
    if cols is None or next(iter(cols.values())).size == 0:
        return None
    return Columns({k: v[:-1] for k, v in cols.items()})
