"""What the three whole-graph reference operations (`ops/pagerank.py`,
`ops/wcc.py`, `ops/sssp.py`) share: the rows INTO every vertex, a memo
for the graph last seen, and the comparison of a reply that holds one
value a vertex.

LDBC Graphalytics defines its algorithms over the edges as the dataset
lists them; an undirected dataset lists a friendship as both directions,
which is what the deployment's generator emits.  Nothing here assumes
that: the rows into a vertex are read off the rows out of the others by
one stable sort, so a hand-made directed graph is answered as directed.
numpy only; imports nothing of the program."""
from __future__ import annotations

import weakref

import numpy as np

_memo = {"graph": None, "held": {}}


def held(ref) -> dict:
    """What the operations keep of the graph last seen (its in-rows, its
    components, one run a start): a second graph empties it."""
    g = _memo["graph"]
    if g is None or g() is not ref:
        _memo.update(graph=weakref.ref(ref), held={})
    return _memo["held"]


def last_seen():
    """The graph the memo is held for, or None."""
    g = _memo["graph"]
    return None if g is None else g()


def sources(csr):
    """The source of every row of a CSR, in its row order."""
    return np.repeat(np.arange(csr.indptr.size - 1, dtype=np.int64), np.diff(csr.indptr))


def in_rows(ref, etype):
    """-> (indptr, src, f): per vertex v the rows u -> v, as the source u
    and the row's double (its weight)."""
    keep = held(ref)
    key = ("in_rows", etype)
    if key not in keep:
        csr = ref.csr[etype]
        order = np.argsort(csr.nbr, kind="stable")
        indptr = np.zeros(ref.n + 1, np.int64)
        np.cumsum(np.bincount(csr.nbr, minlength=ref.n), out=indptr[1:])
        keep[key] = (indptr, sources(csr)[order], csr.f[order])
    return keep[key]


def reduce_rows(ufunc, per_row, indptr):
    """-> (the vertices with a row, `ufunc` over each one's rows):
    `reduceat` cannot reduce an empty segment, so those are left out."""
    has = np.flatnonzero(np.diff(indptr))
    return has, ufunc.reduceat(per_row, indptr[has]) if has.size else per_row[:0]


def paired(reply, want):
    """-> (vertices missing or extra, positions of the shared vids in the
    reply, the same in the reference's answer); a vid that comes twice is
    (how many too many, None, None)."""
    gv, wv = np.asarray(reply.column("vid")).astype(np.int64), want["vid"]
    twice = gv.size - np.unique(gv).size
    if twice:
        return twice, None, None
    _, gi, wi = np.intersect1d(gv, wv, assume_unique=True, return_indices=True)
    return (gv.size - gi.size) + (wv.size - wi.size), gi, wi


def compare_by_vid(reply, want, value):
    """-> (vertices missing, extra, or whose `value` differs: an integer
    exactly, a double where either side is not finite and the two are not
    the same bits; the widest relative gap of a double `value` over the
    vertices both sides hold, or None for an integer; detail).  A double
    has to arrive as float64."""
    got, val = np.asarray(reply.column(value)), want[value]
    is_float = val.dtype.kind == "f"
    if is_float and got.dtype != np.float64:
        return max(int(val.size), 1), None, f"column {value} arrived as {got.dtype}, not float64"
    bad, gi, wi = paired(reply, want)
    if gi is None:
        return bad, None, "a vid more than once"
    a, b = got[gi], val[wi]
    if not is_float:
        bad += int((a.astype(np.int64) != b).sum())
        return bad, None, f"{val.size} vertices, {bad} differ"
    lost = ~(np.isfinite(a) & np.isfinite(b))
    bad += int((lost & (a.view(np.uint64) != b.view(np.uint64))).sum())
    with np.errstate(divide="ignore", invalid="ignore"):
        rel = np.abs(a - b) / np.abs(b)
    rel[(a == b) | lost] = 0.0
    gap = float(rel.max()) if rel.size else 0.0
    return bad, gap, f"{val.size} vertices, {bad} differ, float gap {gap:.3e}"
