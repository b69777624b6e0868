"""`algo.iters_per_stmt` — analytics engine (algo/engine.py): device
iterations a `CALL algo.*` statement ran (Δ`algo_iterations{algo}` over
the window's run ÷ the statements the driver sent): PageRank's fixed
count, and as many as WCC and SSSP took to converge plus the one that
finds nothing changed.  Nothing where no iteration ran."""
from benchmarks.lib.algo_bytes import ALGOS


def read(ctx):
    n = sum(ctx["counter"](f"algo_iterations{{algo={a}}}") for a in ALGOS)
    if not n or not ctx["records"]:
        return None
    return n / len(ctx["records"])
