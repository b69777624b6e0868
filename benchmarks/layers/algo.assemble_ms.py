"""`algo.assemble_ms` — analytics engine (algo/engine.py
`assemble_rows`): the final state array made into `[vid, value]` rows in
vid order, per statement, ms (series `algo_assemble_s`, span
`algo:assemble`): host Python, a list a vertex.  Nothing on a program
without the series."""
from benchmarks.lib.phases import series_ms


def read(ctx):
    return series_ms(ctx, "algo_assemble_s")
