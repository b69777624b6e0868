"""The plain reference: the statements' semantics in numpy over the
generator's arrays.  Imports nothing of the program and takes nothing the
program has made — it is built from a generator's tables
(`reference/generators/<name>.py`) before any cluster or device state
exists.

A graph is one global CSR per edge type (no partitions, no padding).
`dedupe_last` gives INSERT semantics: a later (src, dst) row at rank 0
overwrites an earlier one.  Columns are named as the traffic templates
name them: `d` (destination vid), `w` (int64), `f` (float64).
"""
from __future__ import annotations

import numpy as np


class _Csr:
    __slots__ = ("indptr", "nbr", "w", "f")

    def __init__(self, n, e, dedupe_last):
        src, dst = np.asarray(e["src"], np.int64), np.asarray(e["dst"], np.int64)
        if dedupe_last and src.size:
            key = src * n + dst
            order = np.argsort(key, kind="stable")      # insertion order kept
            last = np.ones(order.size, bool)
            last[:-1] = key[order][1:] != key[order][:-1]
            order = order[last]
        else:
            order = np.argsort(src)         # the order inside a row of the CSR is free
        self.indptr = np.zeros(n + 1, np.int64)
        np.cumsum(np.bincount(src[order], minlength=n), out=self.indptr[1:])
        self.nbr = dst[order]
        self.w = np.asarray(e["w"], np.int64)[order]
        self.f = np.asarray(e["f"], np.float64)[order]


def _slots(csr, frontier):
    """Edge slots out of every frontier vertex, and the parent index."""
    s, e = csr.indptr[frontier], csr.indptr[frontier + 1]
    deg = e - s
    tot = int(deg.sum())
    if tot == 0:
        z = np.empty(0, np.int64)
        return z, z
    parent = np.repeat(np.arange(frontier.size, dtype=np.int64), deg)
    offs = np.arange(tot, dtype=np.int64) - np.repeat(np.cumsum(deg) - deg, deg)
    return s[parent] + offs, parent


class RefGraph:
    def __init__(self, tables, dedupe_last=False):
        """`tables` is a generator's whole output: {"n", "vertex": {prop:
        column}, "edges": {etype: {src, dst, w, f, ...}}}."""
        self.n = int(tables["n"])
        self.vertex = tables.get("vertex", {})
        self.csr = {et: _Csr(self.n, e, dedupe_last) for et, e in tables["edges"].items()}

    def n_edges(self, etype):
        return int(self.csr[etype].nbr.size)

    def out_degree(self, etype):
        return np.diff(self.csr[etype].indptr)

    # -- GO N STEPS ------------------------------------------------------
    def go(self, starts, steps, over, w_gt=None, cols=("d",), count_only=False):
        """N-step expansion: frontiers are de-duplicated between hops,
        the last hop yields one row per edge out of the last frontier,
        and the filter applies to the last hop only (nGQL's GO).
        -> (columns or None, rows, edges expanded per hop)."""
        blks = [self.csr[et] for et in over]
        frontier = np.unique(np.asarray(starts, np.int64))
        hop_edges = []
        for hop in range(steps):
            final = hop == steps - 1
            if final and count_only and w_gt is None:
                n = int(sum((b.indptr[frontier + 1] - b.indptr[frontier]).sum()
                            for b in blks))
                return None, n, hop_edges + [n]
            idx = [_slots(b, frontier)[0] for b in blks]
            hop_edges.append(int(sum(i.size for i in idx)))
            if not final:
                frontier = np.unique(np.concatenate(
                    [b.nbr[i] for b, i in zip(blks, idx)]))
                continue
            out = {"d": np.concatenate([b.nbr[i] for b, i in zip(blks, idx)])}
            need_w = w_gt is not None or "w" in cols
            if need_w:
                out["w"] = np.concatenate([b.w[i] for b, i in zip(blks, idx)])
            if "f" in cols:
                out["f"] = np.concatenate([b.f[i] for b, i in zip(blks, idx)])
            if w_gt is not None:
                keep = out["w"] > w_gt
                out = {k: v[keep] for k, v in out.items()}
            n = int(out["d"].size)
            if count_only:
                return None, n, hop_edges
            return {k: out[k] for k in cols}, n, hop_edges
        return {k: np.empty(0) for k in cols}, 0, hop_edges

    # -- MATCH (p)-[:E]->(f)-[:E]->(ff) WHERE ff.age > a RETURN id(ff), count(*)
    def match_agg(self, starts, etype, min_age):
        """Two-hop path join with trail (distinct-edge) semantics, the
        vertex filter on the end, grouped by the end -> {v, c}."""
        b = self.csr[etype]
        fr = np.unique(np.asarray(starts, np.int64))
        e1, _p1 = _slots(b, fr)
        e2, p2 = _slots(b, b.nbr[e1])
        ff = b.nbr[e2][e2 != e1[p2]]
        ff = ff[np.asarray(self.vertex["age"], np.int64)[ff] > min_age]
        v, c = np.unique(ff, return_counts=True)
        return {"v": v, "c": c.astype(np.int64)}

    # -- MATCH (a)-[e:E*1..N]->(b) RETURN count(*) ---------------------------
    def trail_count(self, starts, etype, max_hop):
        """Variable-length trails (no edge twice within a path)."""
        b = self.csr[etype]
        last = np.unique(np.asarray(starts, np.int64))
        eids, total = [], 0
        for _ in range(max_hop):
            e, parent = _slots(b, last)
            if e.size == 0:
                break
            keep = np.ones(e.size, bool)
            for pe in eids:
                keep &= pe[parent] != e
            total += int(keep.sum())
            sel = np.flatnonzero(keep)
            last = b.nbr[e[sel]]
            eids = [pe[parent[sel]] for pe in eids] + [e[sel]]
        return total

    # -- FIND SHORTEST PATH ---------------------------------------------------
    def _reversed(self, etype):
        key = ("reversed", etype)
        if key not in self.csr:
            b = self.csr[etype]
            src = np.repeat(np.arange(self.n, dtype=np.int64), np.diff(b.indptr))
            self.csr[key] = _Csr(self.n, {"src": b.nbr, "dst": src, "w": b.w, "f": b.f}, False)
        return self.csr[key]

    def bfs_levels(self, src, etype, max_steps, reverse=False):
        b = self._reversed(etype) if reverse else self.csr[etype]
        dist = np.full(self.n, -1, np.int64)
        fr = np.asarray([src], np.int64)
        dist[fr] = 0
        for hop in range(1, max_steps + 1):
            e, _ = _slots(b, fr)
            nxt = np.unique(b.nbr[e])
            fr = nxt[dist[nxt] < 0]
            if fr.size == 0:
                break
            dist[fr] = hop
        return dist

    def shortest_paths(self, src, dst, etype, max_steps):
        """Every shortest src->dst path within max_steps, as sorted
        tuples of vids: the walks that gain a level from src and lose one
        to dst at every step."""
        down = self.bfs_levels(src, etype, max_steps)
        length = int(down[dst])
        if length < 0:
            return []
        up = self.bfs_levels(dst, etype, length, reverse=True)
        b = self.csr[etype]
        paths = [(int(src),)]
        for hop in range(1, length + 1):
            nxt = []
            for p in paths:
                s, e = b.indptr[p[-1]], b.indptr[p[-1] + 1]
                for v in np.unique(b.nbr[s:e]).tolist():
                    if down[v] == hop and up[v] == length - hop:
                        nxt.append(p + (v,))
            paths = nxt
        return sorted(paths)

    # -- GET SUBGRAPH N STEPS FROM v OUT E -----------------------------------
    def subgraph(self, src, etype, steps):
        """Per step: the vertices first reached at that step and every
        out-edge of them whose far end is within `steps` — nGQL's GET
        SUBGRAPH, as ([sorted vids], [sorted (src, dst)]) per row."""
        b = self.csr[etype]
        dist = self.bfs_levels(src, etype, steps)
        rows = []
        for step in range(steps + 1):
            vs = np.flatnonzero(dist == step)
            e, parent = _slots(b, vs)
            d = b.nbr[e]
            keep = dist[d] >= 0
            rows.append((vs.tolist(),
                         sorted(zip(vs[parent[keep]].tolist(), d[keep].tolist()))))
        return rows


# ---------------------------------------------------------------------------
# comparison by content
# ---------------------------------------------------------------------------


def _row_hash(cols):
    h = np.zeros(cols[0].size, np.uint64)
    with np.errstate(over="ignore"):
        for c in cols:
            h = (h ^ c.view(np.uint64)) * np.uint64(0x9E3779B97F4A7C15)
            h ^= h >> np.uint64(29)
    return h


def same_rows(got: dict, want: dict):
    """Multiset comparison of two row tables given as {column: array}.
    -> (rows whose integers differ, widest relative gap of a float column
    or None, detail).  Integer columns are compared exactly.  A float
    column has to arrive as float64 and is compared by its widest
    relative gap |got - want| / |want| over the paired rows (0.0 when bit
    for bit equal); the caller holds that gap to its limit.  A NaN or an
    infinity has no gap to hold: a row in which either side of a float
    pair is not finite, and the two are not the same bits, counts among
    the rows that differ.

    Rows are paired by one single-key sort: on the first float column
    where there is one (the values of an edge property are distinct, and
    a gap far below their spacing keeps their order), else on a 64-bit
    hash of the row.  Where that pairing leaves integers unequal, the
    full lexsort decides, so neither a tie nor a hash collision can fail
    a sound run."""
    names = sorted(want)
    if sorted(got) != names:
        return -1, None, f"columns {sorted(got)} != {names}"
    g = {k: np.asarray(got[k]) for k in names}
    w = {k: np.asarray(want[k]) for k in names}
    n = int(w[names[0]].size)
    if any(int(g[k].size) != n for k in names):
        got_n = int(g[names[0]].size)
        return max(abs(got_n - n), 1), None, f"{got_n} rows != {n}"
    floats = [k for k in names if w[k].dtype.kind == "f"]
    for k in floats:
        if g[k].dtype != np.float64:
            return max(n, 1), None, f"column {k} arrived as {g[k].dtype}, not float64"
    ints = [k for k in names if k not in floats]
    gi = [g[k].astype(np.int64) for k in ints]
    wi = [w[k].astype(np.int64) for k in ints]
    if floats:
        og, ow = np.argsort(g[floats[0]]), np.argsort(w[floats[0]])
    else:
        og, ow = np.argsort(_row_hash(gi), kind="stable"), np.argsort(_row_hash(wi), kind="stable")
    if not all(np.array_equal(a[og], b[ow]) for a, b in zip(gi, wi)):
        og = np.lexsort([g[k] for k in floats[::-1]] + gi[::-1])
        ow = np.lexsort([w[k] for k in floats[::-1]] + wi[::-1])
    bad = np.zeros(n, bool)
    for a, b in zip(gi, wi):
        bad |= a[og] != b[ow]
    gap = None
    for k in floats:
        a, b = g[k][og], w[k][ow]
        lost = ~(np.isfinite(a) & np.isfinite(b))
        bad |= lost & (a.view(np.uint64) != b.view(np.uint64))
        with np.errstate(divide="ignore", invalid="ignore"):
            rel = np.abs(a - b) / np.abs(b)
        rel[(a == b) | lost] = 0.0
        gap = max(gap or 0.0, float(rel.max()) if n else 0.0)
    nb = int(bad.sum())
    return nb, gap, f"{n} rows, {nb} differ" + ("" if gap is None else f", float gap {gap:.3e}")
