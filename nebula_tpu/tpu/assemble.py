"""Row assembly: what the caller of a device statement gets.

The device returns (src, dst, eidx, and rank where something reads it)
per block, kept entries compacted to a prefix, and the fetch
(`fetch.py`) brings each row's kept prefix to the host in pieces.  This
module turns that capture into the statement's result: GO's rows
(`_materialize`) or columns (`_materialize_yields`), MATCH's hop frames
(`_build_frames`).  Property decode happens here, on the host, straight
out of the numpy CsrSnapshot columns at eidx; a property crosses HBM
only when a predicate or a yield needs it.

It also holds what a traversal RETURNS: `TraverseStats`, `HopFrame` and
the two trail joins over frames, which the executors below the runtime
import from here.

Everything here is a function of (host mirror, capture, yields, delta
view): nothing knows the driver, its gate or its caches, and the arrow
never turns round (`tests/unit/test_tpu_arrows.py`).  Which host threads
a statement's assembly may use is the driver's choice and comes in as
`pool_for` (`_assemble`).
"""
from __future__ import annotations

from concurrent.futures import wait as futures_wait
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

from ..core import expr as E
from ..core.value import ColumnarDataSet, Edge
from ..graphstore.csr import (NUMERIC_KINDS, decode_prop_column,
                              decode_prop_column_np)
from ..native.kernels import join_halves as native_join_halves
from ..utils import trace as _t
from ..utils.stats import stats as _metrics
from .exprjit import eval_yield_column_np


class TraverseStats:
    __slots__ = ("hop_edges", "frontier_sizes", "result_edges", "f_cap",
                 "e_cap", "retries", "device_s", "steps",
                 "pin_s", "put_s", "fetch_s", "mat_s", "total_s",
                 "compiles", "hbm_bytes", "segments", "queue_s",
                 "shards", "exchange_bytes", "chunks_run",
                 "chunks_budget", "plan_run", "plan_budget",
                 "fetch_bytes", "fetch_bytes_kept", "bottom_up")

    def __init__(self):
        self.hop_edges: List[int] = []
        self.frontier_sizes: List[int] = []   # popcount entering each hop
        self.result_edges = 0
        self.f_cap = 0
        self.e_cap = 0
        self.retries = 0
        self.device_s = 0.0
        self.steps = 0
        # per-phase wall time (PROFILE device-plane fields)
        self.pin_s = 0.0
        self.put_s = 0.0
        self.fetch_s = 0.0
        self.mat_s = 0.0
        self.total_s = 0.0
        # kernel-ledger fields (ISSUE 8): fresh XLA compiles this run
        # paid for (vs jit-cache hits) and the HBM high-water at
        # dispatch time; `segments` carries per-segment rows for fused
        # pipelines (tpu/pipeline.py fills it)
        self.compiles = 0
        self.hbm_bytes = 0
        self.segments: List[dict] = []
        # dispatch-gate wait before the kernel could run (ISSUE 9):
        # the queue-wait half of the wait-vs-run decomposition
        self.queue_s = 0.0
        # mesh facts (PR 17): part-axis shards this dispatch spanned and
        # the bit-packed frontier all_to_all payload it moved (0 in
        # single-chip local mode — there is no exchange)
        self.shards = 1
        self.exchange_bytes = 0
        # by-need engagement (PR 25, hop.py _by_need): loop trips the
        # hops' per-slot stages ran and the trips their edge budgets
        # hold, summed over hops and parts; both 0 when every hop's
        # budget fits one chunk (straight-line program)
        self.chunks_run = 0
        self.chunks_budget = 0
        # member-plan engagement (PR 29, hop.py _expand_plan): scatter
        # updates the hops' expansion plans issued and what plans over
        # every local vertex issue, summed over hops, blocks and parts;
        # both 0 when every bitmap is narrow enough for the whole-bitmap
        # plan
        self.plan_run = 0
        self.plan_budget = 0
        # bytes the launch's fetches brought to the host, and those of
        # them that are kept capture entries (`_fetch`)
        self.fetch_bytes = 0
        self.fetch_bytes_kept = 0
        # a BFS's levels that went bottom-up (bfs.py's switch), one flag
        # a level; empty for every other program
        self.bottom_up: List[bool] = []

    def edges_traversed(self) -> int:
        return int(sum(self.hop_edges))


class HopFrame:
    """One hop's captured edge set, columnar, indexed for path assembly.

    src/dst: (n,) int64 dense vertex ids in capture order (block-major,
    then part, then per-src CSR slot order — matching the host
    get_neighbors iteration).  Edge OBJECTS are decoded lazily: the
    vectorized trail assembly touches only the entries that land on an
    emitted path, and the full `.edges` object array is built only for
    the DFS consumers (algorithms.py) that ask for it.

    Trail-dedup identity is columnar too: (key_et, key_s, key_d, rank)
    is the canonical physical-edge key (reverse-direction copies of one
    logical edge canonicalize equal), compared component-wise — no
    per-edge Python hashing.
    """
    __slots__ = ("src", "dst", "rank", "n", "order", "_us", "_ustart",
                 "_ucnt", "key_et", "key_s", "key_d",
                 "_segs", "_decode_seg", "_eobjs", "_edone", "_all_done")

    @classmethod
    def empty(cls) -> "HopFrame":
        f = cls()
        f.src = np.empty((0,), np.int64)
        f.dst = np.empty((0,), np.int64)
        f.rank = np.empty((0,), np.int64)
        f.key_et = np.empty((0,), np.int64)
        f.key_s = np.empty((0,), np.int64)
        f.key_d = np.empty((0,), np.int64)
        f.n = 0
        f.order = np.empty((0,), np.int64)
        f._us = np.empty((0,), np.int64)
        f._ustart = np.empty((0,), np.int64)
        f._ucnt = np.empty((0,), np.int64)
        f._segs = []
        f._decode_seg = None
        f._eobjs = np.empty((0,), object)
        f._edone = None
        f._all_done = True
        return f

    @classmethod
    def build(cls, src, dst, rank, key_et, key_s, key_d, segs,
              decode_seg) -> "HopFrame":
        """segs: list of (seg_start, seg_end, payload); decode_seg(
        payload, offsets) -> list[Edge] decodes a segment's entries at
        `offsets` (segment-relative)."""
        if src is None or src.size == 0:
            return cls.empty()
        f = cls()
        f.src, f.dst, f.rank = src, dst, rank
        f.key_et, f.key_s, f.key_d = key_et, key_s, key_d
        f.n = src.size
        f.order = np.argsort(src, kind="stable")
        ss = src[f.order]
        starts = np.flatnonzero(np.concatenate(
            [[True], ss[1:] != ss[:-1]]))
        f._us = ss[starts]
        f._ustart = starts
        f._ucnt = np.diff(np.concatenate([starts, [ss.size]]))
        f._segs = segs
        f._decode_seg = decode_seg
        f._eobjs = None
        f._edone = None
        f._all_done = False
        return f

    def out_edges(self, dense_id: int):
        """Indices (into src/dst/edges) of this hop's edges out of
        dense_id, in CSR order."""
        p = np.searchsorted(self._us, dense_id)
        if p >= self._us.size or self._us[p] != dense_id:
            return ()
        return self.order[self._ustart[p]:self._ustart[p]
                          + self._ucnt[p]]

    def src_slices(self):
        """(us, ustart, ucnt): sorted unique srcs with their slice into
        `order` — the vectorized join's lookup table."""
        return self._us, self._ustart, self._ucnt

    def decode(self, idx: np.ndarray) -> np.ndarray:
        """Edge objects for frame indices `idx` (object array, aligned
        with idx).  Decodes each entry at most once across calls."""
        if self._eobjs is None:
            self._eobjs = np.full((self.n,), None, dtype=object)
            self._edone = np.zeros((self.n,), bool)
        eo = self._eobjs
        if idx.size:
            uniq = np.unique(idx)
            need = uniq[~self._edone[uniq]]
            for (s0, s1, payload) in self._segs:
                m = need[(need >= s0) & (need < s1)]
                if m.size == 0:
                    continue
                eo[m] = self._decode_seg(payload, m - s0)
                self._edone[m] = True
        return eo[idx]

    @property
    def edges(self) -> np.ndarray:
        """All Edge objects (decodes the whole frame once) — the DFS
        consumers' (algorithms.py) contract.  O(1) once fully decoded
        (ADVICE r3: per-access `_edone.all()` made DFS replay O(n²))."""
        if not self._all_done:
            self.decode(np.arange(self.n, dtype=np.int64))
            self._all_done = True
        return self._eobjs


def join_frontier_trails(fr: "HopFrame", last: np.ndarray
                         ) -> Tuple[np.ndarray, np.ndarray]:
    """One searchsorted join of per-trail endpoints against a frame's
    src index.  Returns (parent, fidx): for every (trail, edge)
    continuation, the trail's index into `last` and the frame entry —
    in frame CSR order within each trail.  Shared by the unfused MATCH
    Traverse executor and the fused TpuMatchAgg assembly (single
    source for the join's edge cases)."""
    us, ustart, ucnt = fr.src_slices()
    p = np.searchsorted(us, last)
    p = np.minimum(p, max(us.size - 1, 0))
    hit = us[p] == last
    cnt = np.where(hit, ucnt[p], 0)
    start = np.where(hit, ustart[p], 0)
    ends = np.cumsum(cnt)
    total = int(ends[-1]) if cnt.size else 0
    if total == 0:
        return (np.empty(0, np.int64), np.empty(0, np.int64))
    k = np.arange(total, dtype=np.int64)
    parent = np.searchsorted(ends, k, side="right")
    within = k - (ends[parent] - cnt[parent])
    fidx = fr.order[start[parent] + within]
    return parent, fidx


def trail_distinct_keep(frames: List["HopFrame"], path: List[np.ndarray],
                        parent: np.ndarray, fr: "HopFrame",
                        fidx: np.ndarray) -> np.ndarray:
    """Relationship-uniqueness mask: for each candidate continuation,
    compare the new edge's canonical key against every earlier hop of
    its trail (componentwise over the frames' key columns)."""
    keep = np.ones(fidx.size, bool)
    for eh, pe in enumerate(path):
        pf = frames[eh]
        pidx = pe[parent]
        keep &= ~((pf.key_et[pidx] == fr.key_et[fidx])
                  & (pf.key_s[pidx] == fr.key_s[fidx])
                  & (pf.key_d[pidx] == fr.key_d[fidx])
                  & (pf.rank[pidx] == fr.rank[fidx]))
    return keep


def _d2v(host) -> np.ndarray:
    """Cached dense-id → vid array for batch vid decode (shared by the
    GO materializer and the MATCH frame builder).  INT64 when every vid
    is an int (the common case — object-array gathers over millions of
    result edges cost ~10× an int64 gather), object otherwise."""
    arr = getattr(host, "_d2v_arr", None)
    if arr is None or len(arr) != len(host.dense_to_vid):
        d2v = host.dense_to_vid
        # gate on an ACTUAL int vid: np.asarray would happily parse
        # digit STRINGS ('12' → 12), silently retyping FIXED_STRING
        # results — a space's vids are homogeneous, so one sample
        # decides (None slots are deleted vids → object path)
        sample = next((v for v in d2v if v is not None), None)
        if isinstance(sample, int) and not isinstance(sample, bool):
            try:
                arr = np.asarray(d2v, dtype=np.int64)
            except (TypeError, ValueError, OverflowError):
                arr = np.asarray(d2v, dtype=object)
        else:
            arr = np.asarray(d2v, dtype=object)
        # sequential-int-vid spaces (LDBC-style imports, the array
        # ingest path) have dense == vid: one cached pass here lets the
        # materializers skip a multi-million-row identity gather per
        # query (~0.65 s at north-star scale on the bench host).
        # Identity flag is published BEFORE the array: a concurrent
        # reader that sees the cached array must also see the flag.
        host._d2v_identity = bool(
            arr.dtype.kind == "i"
            and (arr == np.arange(len(arr), dtype=arr.dtype)).all())
        host._d2v_arr = arr
    return arr


def _cap_keys_for_yields(yields, device_props=()) -> Optional[set]:
    """Which capture arrays a yield list reads: a subset of {'src',
    'dst','rank','eidx'} plus 'prop:<name>' for props the kernel
    gathers on device, or None (fetch everything) when a yield isn't
    fully recognized.  Mirrors eval_yield_column_np's access pattern."""
    if yields is None:
        return None
    need = set()
    for e, _ in yields:
        for x in E.walk(e):
            k = x.kind
            # exactly the kinds the fusion gate (exprjit.yieldable)
            # admits — anything else means this walker is stale vs the
            # eval surface, so fetch everything
            if k in ("literal", "function", "edge_prop", "edge"):
                if k == "function":
                    name = getattr(x, "name", "")
                    if name == "src":
                        need.add("src")
                    elif name == "dst":
                        need.add("dst")
                    elif name == "rank":
                        need.add("rank")
                    elif name in ("type", "typeid"):
                        pass             # per-block constants
                    else:
                        return None      # unknown function: fetch all
                elif k == "edge_prop":
                    if x.name == "_rank":
                        need.add("rank")
                    elif x.name == "_src":
                        need.add("src")
                    elif x.name == "_dst":
                        need.add("dst")
                    elif x.name == "_type":
                        pass             # per-block constant
                    elif x.name in device_props:
                        need.add("prop:" + x.name)
                    else:
                        need.add("eidx")
            else:
                return None              # unmodeled expr: fetch all
    return need


def _join_halves(parts, dtype) -> Tuple[np.ndarray, bool]:
    """Fetched pieces of a property column's 32-bit halves, each
    `(2, n)` (device.py `split_halves`), as ONE owned 64-bit column of
    `dtype`, and whether any slot of it holds the kind's NULL sentinel:
    the join rides the pass that concatenates the pieces, and the
    decode's one question rides the join (native/kernels.py
    `join_halves`: one pass a piece)."""
    out = np.empty(sum(a.shape[-1] for a in parts), dtype)
    return out, any([native_join_halves(a, o)
                     for a, o in _piece_slices(parts, out)])


def _piece_slices(parts, out):
    """Each piece beside the slice of `out` it fills, in slot order."""
    at = 0
    for a in parts:
        to = at + a.shape[-1]
        yield a, out[at:to]
        at = to


def _cat_parts(parts, dtype=None):
    """Concatenate per-part kept-prefix slices of a capture array (the
    device compacts kept entries to the front of each part row) —
    contiguous slices instead of a 2D fancy gather, preserving
    (part, slot) order.
    Always returns an owned array: a view of the K-padded capture
    buffer must not escape into long-lived results (it would pin the
    whole bucket for a handful of rows)."""
    if dtype is not None:
        if len(parts) > 1:
            return np.concatenate(parts, dtype=dtype)   # one pass
        return parts[0].astype(dtype)
    if len(parts) > 1:
        return np.concatenate(parts)
    return parts[0].copy()


def _whole(pieces) -> np.ndarray:
    """A fetched capture row (its pieces in slot order) as one array."""
    return pieces[0] if len(pieces) == 1 else np.concatenate(pieces, axis=-1)


def _pieces(rows, perms=None):
    """The fetched rows of one capture column, each its pieces in slot
    order (`_fetch`), as one flat list of pieces; `perms` re-orders
    each row first (the delta plane's canonical CSR order)."""
    if perms is None:
        return [a for pieces in rows for a in pieces]
    return [_whole(pieces) if pm is None else _whole(pieces)[..., pm]
            for pieces, pm in zip(rows, perms)]


def _cat_rows(rows, perms=None, dtype=None):
    """The fetched rows of an identity column (src, dst, rank, eidx) as
    one owned array of `dtype`."""
    return _cat_parts(_pieces(rows, perms), dtype)


def _fill(piece, out) -> bool:
    """One piece-pass into its slice of a column: a property column's
    halves joined (-> the NULL answer), an identity column's piece
    copied into the column's dtype."""
    if piece.ndim == 2:
        return native_join_halves(piece, out)
    out[:] = piece
    return False


def _cat_side_by_side(pool, columns):
    """`columns` ([(pieces, dtype)], each a flat piece list as `_pieces`
    gives it) assembled by `pool`, every piece a task: -> [(column,
    NULL answer)] as `_cat_parts` and `_join_halves` would give them one
    after another.  A pass that raises is the statement's error, once
    every other pass has ended."""
    outs = [np.empty(sum(a.shape[-1] for a in parts),
                     parts[0].dtype if dtype is None else dtype)
            for parts, dtype in columns]
    tasks = [[pool.submit(_fill, a, o) for a, o in _piece_slices(parts, out)]
             for (parts, _), out in zip(columns, outs)]
    futures_wait([f for fs in tasks for f in fs])
    return [(out, any([f.result() for f in fs]))
            for out, fs in zip(outs, tasks)]


def _merged_gather(col, de, name: str, p, e):
    """Column `col` (P, Emax) of a block at part(s) `p` and captured
    edge indices `e`; with a live delta entry `de`, the entries from
    Emax on are the view's numpy mirror's (a delta row carries the
    virtual eidx Emax + slot).  Two gathers: the base column is never
    copied to be extended."""
    emax = col.shape[1]
    if np.ndim(p) == 0:
        col = col[p]            # one part: a row view, then a 1-D take
        late = None if de is None else e >= emax
        if late is None or not late.any():
            return col[e]
        got = col[np.minimum(e, emax - 1)]
        got[late] = de["np"]["d_props"][name][p, e[late] - emax]
        return got
    late = None if de is None else e >= emax
    if late is None or not late.any():
        return col[p, e]
    got = col[p, np.minimum(e, emax - 1)]
    got[late] = de["np"]["d_props"][name][p[late], e[late] - emax]
    return got


def _delta_rows_of(dview, bk):
    """The delta view's entry for block `bk` where the plane HOLDS rows
    of it, else None: what the host steps of a live view follow (the
    identity columns in the fetch, the per-part re-sort, the mirror
    decode).  Tombstones alone need none of them: a dropped base row
    leaves the others in their order."""
    e = None if dview is None else dview[1].get(bk)
    return e if e is not None and any(e["rows"]) else None


def _delta_perms(cap_src, cap_dst, cap_rank, bi, pids, P,
                 d2v_arr, d2v_id, rows):
    """Per-part permutations restoring canonical CSR slot order over
    the merged base+delta capture: within a part, base rows sit in
    (local_src, rank, dst_key) order and delta rows are appended —
    the union must interleave exactly where a full rebuild would
    have placed the new rows.  dst_key matches native.kernels.
    dst_sort_key: the vid itself for int vids, code-point string
    order otherwise (np.unique ordinals preserve it).  Keys are
    unique per live edge, so the sort is deterministic.  A part
    whose delta buffer holds no row (`rows[p]` == 0) keeps its
    order: None in its place; None for all when no part needs one."""
    perms = []
    for p in pids:
        if not rows[p]:
            perms.append(None)
            continue
        s_ = _whole(cap_src[p, bi]).astype(np.int64)
        d_ = _whole(cap_dst[p, bi]).astype(np.int64)
        r_ = _whole(cap_rank[p, bi])
        if d2v_id:
            dk = d_
        else:
            dk = d2v_arr[d_]
            if dk.dtype == object:
                dk = dk.astype("U")
        perms.append(np.lexsort((dk, r_, s_ // P)))
    return perms if any(pm is not None for pm in perms) else None


def _block_columns(store, space: str, dev, block_keys, cap, pool_for,
                   prop_names: Optional[Sequence[str]] = None, dview=None):
    """Vectorized gather of the captured final-hop edge set.

    Yields per-block dicts of flat numpy arrays: sv/dv (vids), rr
    (ranks), decoded prop columns (`decode_prop_column_np`: the column's
    own dtype where it holds no NULL, objects otherwise) — no per-edge
    Python loop; vid decode is one fancy-index into the dense→vid array
    and prop decode is batched per column (VERDICT r1 'weak #3' fix).

    With a live delta view (`dview`, grabbed at dispatch assembly)
    the merged rows are re-sorted per part into canonical CSR order
    and delta-row props decode from the view's numpy mirror at
    virtual eidx = Emax + slot.
    """
    host = dev.host
    d2v_arr = _d2v(host)
    d2v_id = host._d2v_identity
    etype_ids = {et: store.catalog.get_edge(space, et).edge_type
                 for et, _ in block_keys}
    kcount = cap["kcount"]              # (P, nb); arrays (P, nb, K)
    P = kcount.shape[0]
    # what the statement's assembly did, observed once at its end
    # (`tpu_mat_*`): rows assembled and those whose pieces went side
    # by side, numeric columns decoded and those whose NULL answer
    # the assembling pass gave
    rows = pooled_rows = numeric_cols = one_pass_cols = 0
    for bi, (et, dirn) in enumerate(block_keys):
        hb = host.blocks[(et, dirn)]
        de = _delta_rows_of(dview, (et, dirn))
        # kept entries are a device-compacted PREFIX per part row —
        # selection is contiguous slices, not a 2D fancy gather
        # (nonzero + fancy indexing cost ~60% of materialization at
        # north-star scale)
        kc = kcount[:, bi]
        pids = [p for p in range(P) if kc[p] > 0]
        if not pids:
            continue
        n_rows = int(sum(int(kc[p]) for p in pids))
        perms = None
        if de is not None:
            perms = _delta_perms(
                cap["src"], cap["dst"], cap["rank"], bi, pids, P,
                d2v_arr, d2v_id, de["rows"])

        def vids(name, dense):
            if dense is None or d2v_id:
                return dense
            with _t.span("device:materialise.decode", col=name):
                return d2v_arr[dense]

        # arrays the caller's yields never read were not fetched
        # (fetch_keys) — and are not assembled here either; a
        # device-gathered yield column is fetched ready-made, its
        # halves joined as the pieces are concatenated
        names = [n for n in dict.fromkeys(
            hb.props if prop_names is None else prop_names)
            if n in hb.props]
        want = [(k, dt) for k, dt in (("src", np.int64),
                                      ("dst", np.int64), ("rank", None))
                if k in cap]
        want += [("prop:" + n, hb.props[n].dtype) for n in names
                 if ("prop:" + n) in cap]
        got, pooled = _assemble(cap, bi, pids, perms, want, n_rows,
                                pool_for)
        rows += n_rows
        pooled_rows += n_rows * pooled
        ss, dd, rr = (got.get(k, (None,))[0]
                      for k in ("src", "dst", "rank"))
        props = {}
        ee_parts = None
        for n in names:
            pt = hb.prop_types[n]
            if ("prop:" + n) in cap:
                raw, has_null = got["prop:" + n]
            elif "eidx" in cap:
                # the host column at the captured eidx
                raw, has_null = None, None
            else:
                continue
            with _t.span("device:materialise.decode", col=n):
                if raw is None:
                    if ee_parts is None:
                        ee_parts = [_whole(cap["eidx"][p, bi])
                                    for p in pids]
                        if perms is not None:
                            ee_parts = [
                                a if pm is None else a[pm]
                                for a, pm in zip(ee_parts, perms)]
                    raw = [_merged_gather(hb.props[n], de, n, p, e)
                           for p, e in zip(pids, ee_parts)]
                    raw = np.concatenate(raw) if len(raw) > 1 else raw[0]
                props[n] = decode_prop_column_np(
                    pt, raw, host.pool, has_null)
                if pt in NUMERIC_KINDS:
                    numeric_cols += 1
                    one_pass_cols += has_null is not None
        eid = etype_ids[et]
        sv, dv = vids("src", ss), vids("dst", dd)
        yield {"et": et, "dirn": dirn, "etype": eid if dirn == "out"
               else -eid, "n": n_rows, "sv": sv, "dv": dv,
               "rr": rr, "props": props,
               "prop_types": hb.prop_types}
    m = _metrics()
    m.add_value("tpu_mat_rows", rows)
    m.add_value("tpu_mat_pooled_rows", pooled_rows)
    m.add_value("tpu_mat_numeric_cols", numeric_cols)
    m.add_value("tpu_mat_one_pass_cols", one_pass_cols)


def _assemble(cap, bi, pids, perms, want, n_rows, pool_for):
    """The fetched pieces of block `bi`'s columns `want` ([(capture
    key, host dtype)]) joined into owned columns: -> ({key: (column,
    a property column's NULL answer)}, whether side by side).  One
    after another, a span a column (`mat_concat`), as a rule; side
    by side under ONE span where the caller lends a pool for a block
    of this many rows (`pool_for(n_rows)` -> a pool or None: the
    driver's policy, tpu/runtime.py `_pool_for`) and the rows keep
    their order (a delta plane's re-sort gathers a whole row first)."""
    def pieces(key):
        return _pieces([cap[key][p, bi] for p in pids], perms)

    pool = pool_for(n_rows) if perms is None else None
    if pool is not None:
        with _t.span("device:materialise.concat", col="*",
                     pooled=len(want)):
            return dict(zip((k for k, _ in want), _cat_side_by_side(
                pool, [(pieces(k), dt) for k, dt in want]))), True
    got = {}
    for key, dt in want:
        with _t.span("device:materialise.concat", col=key):
            got[key] = (_join_halves(pieces(key), dt)
                        if key.startswith("prop:")
                        else (_cat_parts(pieces(key), dt), False))
    return got, False


def _materialize(store, space: str, dev, block_keys, cap, pool_for,
                 dview=None) -> List[Tuple[Any, Optional[Edge], Any]]:
    """(src_vid, Edge, dst_vid) triples — Edge objects built in one
    tight zip loop over pre-decoded columns."""
    rows: List[Tuple[Any, Optional[Edge], Any]] = []
    for b in _block_columns(store, space, dev, block_keys, cap, pool_for,
                            dview=dview):
        et, etype = b["et"], b["etype"]
        names = list(b["props"])
        cols = [b["props"][n].tolist() for n in names]
        rr = b["rr"].tolist()
        for i, (sv, dv) in enumerate(zip(b["sv"].tolist(),
                                         b["dv"].tolist())):
            props = {n: c[i] for n, c in zip(names, cols)}
            rows.append((sv, Edge(sv, dv, et, rr[i], props,
                                  etype=etype), dv))
    return rows


def _materialize_yields(store, space: str, dev, block_keys, cap, yields,
                        pool_for, dview=None) -> ColumnarDataSet:
    """Final output as a lazy columnar DataSet (fused Project).

    Columns are numpy arrays straight from the capture buffers; no
    per-row Python objects are built here — the ColumnarDataSet
    materializes rows only if the consumer crosses the row boundary
    (VERDICT r2 item 3: device results stay columnar end-to-end)."""
    needed = [x.name for e, _ in yields for x in E.walk(e)
              if x.kind == "edge_prop"]
    per_block: List[List[np.ndarray]] = []
    for b in _block_columns(store, space, dev, block_keys, cap, pool_for,
                            prop_names=needed, dview=dview):
        per_block.append([eval_yield_column_np(e, b)
                          for e, _ in yields])
    names = [alias for _, alias in yields]
    if not per_block:
        return ColumnarDataSet(
            names, [np.empty(0, object) for _ in yields])
    if len(per_block) == 1:
        return ColumnarDataSet(names, per_block[0])

    def _cat(j):
        # ADVICE r3: int+float blocks (multi-etype GO) must not
        # upcast to float64 — that silently turns 5 into 5.0 and
        # diverges from the host path's exact per-element types.
        # Mixed numeric kinds concatenate as object instead.
        blks = [blk[j] for blk in per_block]
        kinds = {b.dtype.kind for b in blks}
        if len(kinds) > 1 and "O" not in kinds:
            blks = [b.astype(object) for b in blks]
        return np.concatenate(blks)

    return ColumnarDataSet(names, [_cat(j)
                                   for j in range(len(yields))])


def _build_frames(store, space: str, dev, block_keys, cap, steps: int,
                  dview=None) -> List["HopFrame"]:
    """cap arrays are (P, steps, nb, EB); one columnar HopFrame per
    hop.  NO Edge objects are built here — frames carry dense-id and
    canonical-key columns, plus a per-segment decode closure that
    materializes Edge objects only for the entries the assembly
    actually emits (VERDICT r2 item 4)."""
    host = dev.host
    d2v_arr = _d2v(host)
    d2v_id = host._d2v_identity
    etype_ids = {et: store.catalog.get_edge(space, et).edge_type
                 for et, _ in block_keys}
    def make_decode(et, dirn, sgn):
        hb = host.blocks[(et, dirn)]
        de = _delta_rows_of(dview, (et, dirn))

        def decode_seg(payload, offs):
            ss, dd, rr, ee, sel_p = payload
            ss, dd = ss[offs], dd[offs]
            rr, ee, sp = rr[offs], ee[offs], sel_p[offs]
            props = {n: decode_prop_column(
                hb.prop_types[n],
                _merged_gather(hb.props[n], de, n, sp, ee), host.pool)
                for n in hb.props}
            sv = ss if d2v_id else d2v_arr[ss]
            dvv = dd if d2v_id else d2v_arr[dd]
            names = list(props)
            cols = [props[n] for n in names]
            rrl = rr.tolist()
            return [Edge(s, d, et, rrl[i],
                         {n: c[i] for n, c in zip(names, cols)},
                         etype=sgn)
                    for i, (s, d) in enumerate(zip(sv.tolist(),
                                                   dvv.tolist()))]
        return decode_seg

    def decode_seg(payload_dec, offs):
        payload, dec = payload_dec
        return dec(payload, offs)

    frames = []
    P = cap["kcount"].shape[0]
    for h in range(steps):
        srcs, dsts, rks = [], [], []
        ket, ks, kd = [], [], []
        segs = []
        pos = 0
        for bi, (et, dirn) in enumerate(block_keys):
            kc = cap["kcount"][:, h, bi]        # (P,)
            # kept entries are a device-compacted prefix per part
            # row: per-part slice concat preserves the (part, slot)
            # order nonzero gave — per (part, src) the kept slots
            # stay contiguous ascending eidx, so the concat below is
            # already (src-stable) CSR order
            pids = [p for p in range(kc.shape[0]) if kc[p] > 0]
            if not pids:
                continue
            perms = None
            de = _delta_rows_of(dview, (et, dirn))
            if de is not None:
                perms = _delta_perms(
                    cap["src"][:, h], cap["dst"][:, h],
                    cap["rank"][:, h], bi, pids, P,
                    d2v_arr, d2v_id, de["rows"])

            def catp(name, dtype=None):
                with _t.span("device:materialise.concat", col=name):
                    return _cat_rows(
                        [cap[name][p, h, bi] for p in pids], perms, dtype)

            ss = catp("src", np.int64)
            dd = catp("dst", np.int64)
            rr = catp("rank", np.int64)
            ee = catp("eidx")
            sel_p = np.repeat(np.asarray(pids, np.int64),
                              [int(kc[p]) for p in pids])
            eid = etype_ids[et]
            sgn = eid if dirn == "out" else -eid
            srcs.append(ss)
            dsts.append(dd)
            rks.append(rr)
            # canonical physical-edge key: out/in copies of one
            # logical edge compare equal (trail dedup currency)
            ket.append(np.full(ss.size, eid, np.int64))
            ks.append(ss if dirn == "out" else dd)
            kd.append(dd if dirn == "out" else ss)
            segs.append((pos, pos + ss.size,
                         ((ss, dd, rr, ee, sel_p),
                          make_decode(et, dirn, sgn))))
            pos += ss.size
        if not srcs:
            frames.append(HopFrame.empty())
            continue
        frames.append(HopFrame.build(
            np.concatenate(srcs), np.concatenate(dsts),
            np.concatenate(rks), np.concatenate(ket),
            np.concatenate(ks), np.concatenate(kd),
            segs, decode_seg))
    return frames
