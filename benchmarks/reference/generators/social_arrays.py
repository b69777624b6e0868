"""Generator `social_arrays`: KNOWS edge arrays with a Zipf(1.6) in-tail
on 15% of the edges and a Zipf(1.5) OUT-tail (celebrity sources) on 5%,
parallel edges kept — the benchmark's own copy of
nebula_tpu/bench/datagen.py `make_social_arrays`, same draws in the same
order.  Imports nothing of the program."""
from __future__ import annotations

import numpy as np

from benchmarks.reference.generators.snb_tables import NAMES


def generate(sizes: dict, seed: int) -> dict:
    """-> {"n", "vertex": {}, "strings": {city: names}, "edges": {KNOWS:
    {src, dst, w, f, city}}}; `city` is an index into `strings.city`."""
    n_persons, avg_degree = int(sizes["persons"]), int(sizes["degree"])
    rng = np.random.default_rng(seed)
    n_edges = n_persons * avg_degree
    src = rng.integers(0, n_persons, n_edges, dtype=np.int64)
    dst = rng.integers(0, n_persons, n_edges, dtype=np.int64)
    hot = rng.random(n_edges) < 0.15
    dst[hot] = (rng.zipf(1.6, int(hot.sum())) - 1) % n_persons
    shot = rng.random(n_edges) < 0.05
    src[shot] = (rng.zipf(1.5, int(shot.sum())) - 1) % n_persons
    keep = src != dst
    src, dst = src[keep], dst[keep]
    n_edges = src.size
    return {"n": n_persons, "vertex": {}, "strings": {"city": NAMES},
            "edges": {"KNOWS": {
                "src": src, "dst": dst,
                "w": rng.integers(0, 100, n_edges, dtype=np.int64),
                "f": rng.random(n_edges),
                "city": rng.integers(0, len(NAMES), n_edges, dtype=np.int64)}}}
