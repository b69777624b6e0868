"""`algo.prepare_ms` — analytics engine (algo/engine.py `_algo_graph`,
`_by_dst`): graph preparation per statement, ms: the snapshot's blocks
flattened into one edge list and that list's stable sort by destination
(series `algo_prepare_s`, span `algo:prepare`), which the engine keeps
for up to four (snapshot, block set, weight) at a time.  0 in a window
whose warm-up prepared every graph; what it reads otherwise is a cache
that did not hold.  Nothing on a program without the series."""
from benchmarks.lib.phases import series_ms


def read(ctx):
    return series_ms(ctx, "algo_prepare_s")
