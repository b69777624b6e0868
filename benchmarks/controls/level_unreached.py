"""Control `level_unreached`: one reached vertex reported as out of reach
(-1), what a level body that drops a mark hands back.  The check refuses
it by `rows_mismatched` (limit 0)."""
from benchmarks.controls.level_off_by_one import reached
from benchmarks.lib.reply import Columns, columns_of


def broken(want):
    cols = columns_of(want)
    found = cols and reached(cols)
    if not found:
        return None
    k, at = found
    changed = cols[k].copy()
    changed[at[-1]] = -1
    return Columns({**cols, k: changed})
