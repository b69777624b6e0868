"""Reference operation `sssp`: LDBC Graphalytics' SSSP (specification
v1.0, section 2.3.6): for every vertex the least sum of weights over the
paths from the source that follow the rows' direction, in float64; a
vertex no path reaches is ABSENT from the answer (the suite writes it as
infinity, the program's statement leaves its row out).  Weights are not
negative; a zero weight is an edge like any other.

Bellman-Ford by rounds over the rows INTO every vertex (a round gives a
vertex the least of its distance and source's distance + weight over its
in-rows) until a round changes nothing: as many rounds as the longest
least path has rows, plus one.  A distance is a left-to-right sum of the
weights along its path, in the order any relaxation adds them, so the same
path gives the same double whatever computes it.

The rows of the reply are counted without the weights: a vertex is
reached when a path of rows leads to it (`count`, by rounds over the same
in-rows with a mask in the distances' place), which is what the traffic
generator asks of every candidate source.  numpy only; imports nothing of
the program."""
from __future__ import annotations

import numpy as np

from benchmarks.reference.whole_graph import (compare_by_vid, held, in_rows, last_seen,
                                              reduce_rows)


def reached(ref, etype, start):
    """Mask of the vertices some path of rows from `start` leads to."""
    keep = held(ref)
    key = ("reached", etype, int(start))
    if key not in keep:
        into, src, _ = in_rows(ref, etype)
        mask = np.zeros(ref.n, bool)
        mask[start] = True
        while True:
            has, hit = reduce_rows(np.logical_or, mask[src], into)
            new = has[hit & ~mask[has]]
            if new.size == 0:
                break
            mask[new] = True
        keep[key] = mask
    return keep[key]


def distances(ref, etype, start):
    keep = held(ref)
    key = ("distances", etype, int(start))
    if key not in keep:
        into, src, weight = in_rows(ref, etype)
        dist = np.full(ref.n, np.inf)
        dist[start] = 0.0
        while True:
            has, least = reduce_rows(np.minimum, dist[src] + weight, into)
            closer = least < dist[has]
            if not closer.any():
                break
            dist[has[closer]] = least[closer]
        keep[key] = dist
    return keep[key]


def profile(t, start):
    """What lib/algo_bytes.py reckons a run's bytes from: the rows out of
    the vertices `start` reaches on the graph last seen; None where
    neither its reach nor its distances were ever asked for."""
    ref = last_seen()
    (et,) = t["params"]["edge_types"]
    keep = {} if ref is None else held(ref)
    if ("distances", et, int(start)) in keep:
        mask = np.isfinite(keep["distances", et, int(start)])
    elif ("reached", et, int(start)) in keep:
        mask = keep["reached", et, int(start)]
    else:
        return None
    out = np.diff(ref.csr[et].indptr)
    return {"algo": "sssp", "rows": int(out[mask].sum()), "vertices": ref.n}


def answer(ref, t, start):
    (et,) = t["params"]["edge_types"]
    dist = distances(ref, et, start)
    vid = np.flatnonzero(np.isfinite(dist))
    return {"vid": vid, "distance": dist[vid]}


def count(ref, t, start):
    (et,) = t["params"]["edge_types"]
    return int(reached(ref, et, start).sum())


def compare(reply, want):
    return compare_by_vid(reply, want, "distance")
