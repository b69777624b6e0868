"""Generator `datagen_fb_weighted`: an undirected friendship graph with ONE
double weight a friendship, what an LDBC Graphalytics datagen dataset is
in shape (undirected, weighted, a person's friends capped), not datagen's
Facebook degree curve nor its correlated windows.

The degrees and the pairs are `knows_symmetric`'s, by import: its whole
draw is made and only `src` and `dst` kept (its per-row `w`, `f` and `city`
are each direction's own, which is wrong for a shortest path over an
undirected graph: a friendship would weigh one thing there and another
back).  Pair i is row i (a -> b) and row i + pairs (b -> a), and both carry
`weight[i]`, uniform in (0, 1], drawn from a stream of its own
(`default_rng([seed, TAG])`) so that the pair draw stays the imported one's.

Besides `weight` the rows carry the two columns reference/graph.py's CSR
keeps of every edge table: `f`, the one double a row, IS the weight (the
same array: the whole-graph reference operations read it there), and `w`
is a broadcast zero nobody reads.  numpy only; imports nothing of the
program."""
from __future__ import annotations

import numpy as np

from benchmarks.reference.generators import knows_symmetric

TAG = 0x77656967        # "weig"


def generate(sizes: dict, seed: int) -> dict:
    """-> {"n", "vertex": {}, "strings": {}, "edges": {KNOWS: {src, dst,
    weight, f, w}}}; row i and row i + rows/2 are the two directions of
    one friendship and carry one weight."""
    drawn = knows_symmetric.generate(sizes, seed)
    e = drawn["edges"]["KNOWS"]
    src, dst = e["src"], e["dst"]
    pairs = src.size // 2
    one = 1.0 - np.random.default_rng([int(seed), TAG]).random(pairs)     # (0, 1]
    weight = np.concatenate([one, one])
    return {"n": drawn["n"], "vertex": {}, "strings": {},
            "edges": {"KNOWS": {"src": src, "dst": dst, "weight": weight, "f": weight,
                                "w": np.broadcast_to(np.int64(0), src.shape)}}}
