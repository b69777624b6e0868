"""Reference operation `pagerank`: LDBC Graphalytics' PR (specification
v1.0, section 2.3.3).  Every vertex starts at 1/n; each of a FIXED number
of iterations gives a vertex

    (1 - d) / n + d * (sum over rows u -> v of PR(u) / outdegree(u)
                       + sum over dangling w of PR(w) / n)

so the mass of the vertices with no row out is spread evenly and the ranks
keep summing to one.  `n` counts every vertex of the tables, rows or none.
float64 throughout; a row is an edge, so a pair listed twice counts twice.
The start vertex the traffic generator draws means nothing here.  numpy
only; imports nothing of the program."""
from __future__ import annotations

import numpy as np

from benchmarks.reference.whole_graph import compare_by_vid, held, last_seen, sources


def ranks(ref, etype, damping, iterations):
    csr = ref.csr[etype]
    n = ref.n
    outdeg = np.diff(csr.indptr).astype(np.float64)
    src = sources(csr)
    dangling = outdeg == 0
    rank = np.full(n, 1.0 / n)
    for _ in range(int(iterations)):
        share = np.divide(rank, outdeg, out=np.zeros(n), where=~dangling)
        into = np.bincount(csr.nbr, weights=share[src], minlength=n)
        rank = (1.0 - damping) / n + damping * (into + rank[dangling].sum() / n)
    return rank


def _run(ref, t):
    p = t["params"]
    (et,) = p["edge_types"]
    key = ("pagerank", et, float(p["damping"]), int(p["max_iter"]))
    keep = held(ref)
    if key not in keep:
        keep[key] = ranks(ref, et, float(p["damping"]), int(p["max_iter"]))
    return keep[key]


def profile(t, start):
    """What lib/algo_bytes.py reckons a run's bytes from, on the graph
    last seen; None before any."""
    ref = last_seen()
    if ref is None:
        return None
    (et,) = t["params"]["edge_types"]
    return {"algo": "pagerank", "iterations": int(t["params"]["max_iter"]),
            "rows": ref.n_edges(et), "vertices": ref.n}


def answer(ref, t, start):
    return {"vid": np.arange(ref.n, dtype=np.int64), "rank": _run(ref, t)}


def count(ref, t, start):
    return ref.n


def compare(reply, want):
    return compare_by_vid(reply, want, "rank")
