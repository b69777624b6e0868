"""`algo.iter_ms` — analytics engine (algo/engine.py `_iterate`): the
mean of one vertex-program iteration on the host's clock, the gate, the
kernel's run to `block_until_ready` and the fetch of its convergence
scalars: Δ`algo_iter_us{algo}.sum` ÷ Δ`algo_iter_us{algo}.count` ÷ 1,000
over the window's run, the three algorithms together (the builder prints
each apart).  Nothing where no iteration ran."""
from benchmarks.lib.algo_bytes import ALGOS


def read(ctx):
    n = sum(ctx["counter"](f"algo_iter_us{{algo={a}}}.count") for a in ALGOS)
    if not n:
        return None
    return sum(ctx["counter"](f"algo_iter_us{{algo={a}}}.sum") for a in ALGOS) / n / 1e3
