"""Reference operation `wcc`: LDBC Graphalytics' WCC (specification v1.0,
section 2.3.4): the partition of the vertices into the connected
components of the graph with every row read both ways.  Every vertex of
the tables is in the answer, one with no row as a component of its own.

The answer names a component by its smallest vid, which is also what the
program documents (`algo/__init__.py`), but the comparison is first by the
PARTITION, as the suite validates WCC (two labellings are the same answer
when some bijection of the labels maps one onto the other): a vertex
differs when the set of vertices that share its label is not the
reference's set, and then also when it is labelled otherwise than by that
smallest vid.

Components by min-label propagation with pointer jumping: a round gives a
vertex the smallest label among itself and the far ends of its rows, out
and in, and then the label of its label; a label is always a vid of the
vertex's own component and never above the vertex's own, so the fixpoint
is the component's smallest vid.  numpy only; imports nothing of the
program."""
from __future__ import annotations

import numpy as np

from benchmarks.reference.whole_graph import held, in_rows, last_seen, paired, reduce_rows


def components(ref, etype):
    keep = held(ref)
    key = ("components", etype)
    if key not in keep:
        csr = ref.csr[etype]
        into, src, _ = in_rows(ref, etype)
        label = np.arange(ref.n, dtype=np.int64)
        while True:
            new = label.copy()
            for indptr, far in ((csr.indptr, csr.nbr), (into, src)):
                has, least = reduce_rows(np.minimum, label[far], indptr)
                new[has] = np.minimum(new[has], least)
            new = new[new]
            if np.array_equal(new, label):
                break
            label = new
        keep[key] = label
    return keep[key]


def profile(t, start):
    """What lib/algo_bytes.py reckons a run's bytes from, on the graph
    last seen; None before any."""
    ref = last_seen()
    if ref is None:
        return None
    (et,) = t["params"]["edge_types"]
    return {"algo": "wcc", "rows": ref.n_edges(et), "vertices": ref.n}


def answer(ref, t, start):
    (et,) = t["params"]["edge_types"]
    return {"vid": np.arange(ref.n, dtype=np.int64), "component": components(ref, et)}


def count(ref, t, start):
    return ref.n


def _classes(labels):
    """-> (a dense code per vertex, the size of each vertex's class)."""
    _, code, counts = np.unique(labels, return_inverse=True, return_counts=True)
    return code, counts[code]


def compare(reply, want):
    """-> (vertices missing, extra, in another class of the partition
    than the reference's, or not labelled by their class's smallest vid;
    None; detail)."""
    bad, gi, wi = paired(reply, want)
    if gi is None:
        return bad, None, "a vid more than once"
    comp = want["component"]
    a, b = np.asarray(reply.column("component")).astype(np.int64)[gi], comp[wi]
    # the same set: my class is as large on either side, and so is what the two share
    (ca, size_a), (cb, size_b) = _classes(a), _classes(b)
    _, shared = _classes(ca * (int(cb.max(initial=0)) + 1) + cb)
    same_set = (size_a == shared) & (size_b == shared)
    split = int((~same_set).sum())
    relabelled = int((same_set & (a != b)).sum())
    bad += split + relabelled
    return bad, None, (f"{comp.size} vertices, {np.unique(comp).size} components: {split} in "
                       f"another class, {relabelled} under another label")
