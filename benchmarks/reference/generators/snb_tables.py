"""Generator `snb_tables`: Person rows plus KNOWS and LIKES edge rows —
the benchmark's own copy of nebula_tpu/bench/datagen.py `write_snb_csvs`
without the files, so that a later PR cannot change the yardstick by
changing the program's generator.  Same draws from the same
`numpy.random.default_rng(seed)` in the same order.  Imports nothing of
the program."""
from __future__ import annotations

import numpy as np

NAMES = ["ada", "bob", "cid", "dee", "eve", "fay", "gus", "hal",
         "ivy", "joe", "kim", "lee", "mia", "ned", "oda", "pam"]


def generate(sizes: dict, seed: int) -> dict:
    """-> {"n", "vertex": {age, name}, "edges": {KNOWS, LIKES: {src, dst,
    w, f}}}.  LIKES is a fifth of KNOWS; destinations are uniform with a
    Zipf(1.6) head on 15% of the edges; self-loops are dropped.  A later
    (src, dst) pair overwrites an earlier one when the rows are INSERTed
    (rank 0), so the served graph has fewer edges than rows."""
    n_persons, avg_degree = int(sizes["persons"]), int(sizes["degree"])
    rng = np.random.default_rng(seed)
    ages = rng.integers(13, 90, n_persons)
    name_ix = rng.integers(0, len(NAMES), n_persons)

    def edges(n_edges):
        src = rng.integers(0, n_persons, n_edges)
        dst = rng.integers(0, n_persons, n_edges)
        hot = rng.random(n_edges) < 0.15
        dst[hot] = (rng.zipf(1.6, int(hot.sum())) - 1) % n_persons
        keep = src != dst
        src, dst = src[keep], dst[keep]
        return {"src": src.astype(np.int64), "dst": dst.astype(np.int64),
                "w": rng.integers(0, 100, src.size).astype(np.int64),
                "f": rng.random(src.size)}

    knows = edges(n_persons * avg_degree)
    likes = edges(max(n_persons * avg_degree // 5, 1))
    return {"n": n_persons,
            "vertex": {"age": ages.astype(np.int64),
                       "name": [NAMES[i] for i in name_ix.tolist()]},
            "edges": {"KNOWS": knows, "LIKES": likes}}
