"""The shared frontier-iteration step (ISSUE 13 satellite).

Before this module, the per-level "expand the frontier bitmap through
every CSR block, apply the predicate, mark candidate destinations"
body lived INSIDE tpu/bfs.py's two kernel builders (local and sharded),
so any new frontier-style program would have re-implemented it.  The
step now lives here, defined once:

  * `expand_part`        — one part × one block expansion + predicate
                           mask (the former bfs `one_part`, including
                           the bottom-up endpoint swap);
  * `top_down_step`      — single-chip level body: expand every block
                           from the frontier bitmap, OR the ownership
                           marks (the degenerate all_to_all);
  * `bottom_up_step`     — single-chip direction-optimizing level body:
                           unvisited vertices scan their REVERSE
                           adjacency against the resident frontier
                           bitmap (no routing exchange at all);
  * `sharded_level_step` — the shard_map level body: expand + mark,
                           the caller exchanges marks over ICI.

tpu/bfs.py composes its kernels from these; the vertex-program engine
(algo/engine.py) drives its frontier-style algorithms through the same
helpers when a program is expansion-shaped (the dense whole-edge-list
algorithms — PageRank's SpMV — use the flat form in algo/graph.py
instead, which has no frontier to expand).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..tpu.hop import (_delta_cap, _delta_live, _drop_live_tombstones,
                       _expand_block, _live_rows, _mark, _mark_rows,
                       take_halves)

__all__ = ["expand_part", "top_down_step", "bottom_up_step",
           "sharded_level_step", "delta_live"]


def _keep(block, src, dst, rk, eidx, ve, pred, pred_cols,
          swap_ends: bool = False):
    """The compiled edge predicate over one part's expanded slots,
    folded into the valid mask.  `swap_ends` is the bottom-up contract:
    $^/$$ are TRAVERSAL source/destination, and bottom-up expands the
    REVERSE adjacency, so the expansion source is the traversal
    DESTINATION (the newly reached vertex) and the neighbor is the
    frontier side — the endpoint columns the predicate sees are
    swapped."""
    if pred is None:
        return ve
    ps, pd = (dst, src) if swap_ends else (src, dst)
    cols = {"_rank": rk, "_src": ps, "_dst": pd}
    for name in pred_cols:
        if not name.startswith("_"):
            cols[name] = take_halves(block["props"][name], eidx)
    return pred(cols) & ve


def expand_part(block, fbm, pid, EB: int, P: int, vmax: int,
                pred=None, pred_cols=(), hub_dense=None,
                swap_ends: bool = False):
    """Expand ONE part's frontier bitmap through ONE block's BASE CSR
    and apply the compiled edge predicate (`_keep`).  The delta plane
    is the level bodies' to merge (`_block_marks`): bottom-up never
    sees it — a level goes top-down while the plane holds anything (the
    reverse adjacency has no delta).

    Returns (src, dst, keep, total, ovf) per the _expand_block slot
    contract with the predicate folded into `keep`."""
    src, dst, rk, eidx, ve, total, ovf = _expand_block(
        block["indptr"], block["nbr"], block["rank"], fbm, EB, P,
        pid, vmax_local=vmax, hub_dense=hub_dense)
    return src, dst, _keep(block, src, dst, rk, eidx, ve, pred,
                           pred_cols, swap_ends), total, ovf


def delta_live(blocks_data):
    """Whether any block's armed delta plane holds a row or a
    tombstone (a traced scalar; False where no plane is armed)."""
    live = jnp.zeros((), bool)
    for b in blocks_data:
        if _delta_cap(b):
            tomb, rows = _delta_live(b)
            live = live | tomb | rows
    return live


def _block_marks(over, b, efbm, pid, EB: int, P: int, vmax: int,
                 pred, pred_cols, hub_dense, acc=None):
    """One block's top-down level: expand the base CSR over all EB
    slots, merge an armed delta plane BY WHAT IT HOLDS (ISSUE 19; the
    stages of hop.py's `_traverse`: tombstoned base slots dropped, the
    plane's rows marked beside the base's, each behind the plane's live
    count, so that an empty plane costs a level what no plane costs),
    apply the predicate, mark the destinations.  `over` maps a per-part
    function over the layout's leading axes as in `_traverse`; `acc` is
    a mark matrix to mark into.

    -> (marks, edges, ovf)"""
    dcap = _delta_cap(b)
    src, dst, rk, eidx, ve, total, ovf = over(
        lambda blk, pd, f: _expand_block(
            blk["indptr"], blk["nbr"], blk["rank"], f, EB, P, pd,
            vmax_local=vmax, hub_dense=hub_dense))(b, pid, efbm)
    if dcap:
        has_tomb, has_rows = _delta_live(b)
        ve = _drop_live_tombstones(over, b, pid, eidx, ve, has_tomb)
    keep = over(lambda blk, _p, *a: _keep(blk, *a, pred, pred_cols))(
        b, pid, src, dst, rk, eidx, ve)
    if acc is None:
        marks = over(lambda _b, _p, d, k: _mark(d, k, P, vmax))(
            None, None, dst, keep)
    else:
        marks = over(lambda _b, _p, m, d, k: _mark(d, k, P, vmax, m))(
            None, None, acc, dst, keep)
    if dcap:
        _s, tdst, _r, tkeep, tact = _live_rows(
            over, b, pid, efbm, P, has_rows, rk.dtype, pred,
            [c for c in pred_cols if not c.startswith("_")])
        marks = _mark_rows(over, marks, tdst, tkeep, P, vmax, has_rows)
        total = total + jnp.sum(tact, axis=-1, dtype=jnp.int32)
    return marks, total, ovf


def top_down_step(blocks_data, efbm, EB: int, P: int, vmax: int, pids,
                  pred=None, pred_cols=(), hub_dense=None):
    """Single-chip level body, forward direction: expand every block
    from the (possibly hub-extended) frontier bitmap `efbm`, mark
    destinations in the (P, vmax) ownership bitmap, OR-reduce the
    per-source mark matrices (the degenerate all_to_all).

    -> (cand (P, vmax) bool, edges (P,) i32, ovf (P,) bool)."""
    marks = None
    edges = jnp.zeros((P,), jnp.int32)
    ovf = jnp.zeros((P,), bool)
    for b in blocks_data:
        # vmap the whole block dict: every leaf (indptr/nbr/rank/props
        # and the d_* delta plane when present) has a leading part axis
        blk_marks, total, ov = _block_marks(
            jax.vmap, b, efbm, pids, EB, P, vmax, pred, pred_cols,
            hub_dense)
        ovf = ovf | ov
        edges = edges + total
        marks = blk_marks if marks is None else marks | blk_marks
    return marks.any(axis=0), edges, ovf


def bottom_up_step(blocks_data, fbm, eunvis, EB: int, P: int,
                   vmax: int, pids, pred=None, pred_cols=(),
                   hub_dense=None):
    """Single-chip direction-optimizing level body: expand the REVERSE
    adjacency of unvisited vertices (`eunvis`, hub-extended by the
    caller); a vertex joins the frontier if any in-neighbor's bit is
    set in the resident frontier bitmap `fbm`.  Needs NO routing
    exchange: each owner decides its own vertices from the global
    bitmap.

    -> (cand (P, vmax) bool, edges (P,) i32, ovf (P,) bool)."""
    cand = jnp.zeros((P, vmax), bool)
    edges = jnp.zeros((P,), jnp.int32)
    ovf = jnp.zeros((P,), bool)
    for bi in range(len(blocks_data)):
        b = blocks_data[bi]
        src, nb, keep, total, ov = jax.vmap(
            lambda ip, nbr, rkk, prp, f, pd: expand_part(
                {"indptr": ip, "nbr": nbr, "rank": rkk,
                 "props": prp}, f, pd, EB, P, vmax,
                pred=pred, pred_cols=pred_cols, hub_dense=hub_dense,
                swap_ends=True)
        )(b["rev_indptr"], b["rev_nbr"], b["rev_rank"],
          b.get("rev_props", {}), eunvis, pids)
        ovf = ovf | ov
        edges = edges + total
        member = fbm[nb % P, nb // P] & keep       # (P, EB)
        # route the reached vertex to its OWNER row (a degree-split
        # hub row's src belongs to another part, so the plain
        # local-index scatter would mis-home it)
        blk = jax.vmap(lambda s, m: _mark(s, m, P, vmax))(src, member)
        cand = cand | blk.any(axis=0)
    return cand, edges, ovf


def sharded_level_step(blocks_data, efbm, EB: int, P: int, pid,
                       vmax: int, pred=None, pred_cols=(),
                       hub_dense=None):
    """shard_map level body (one part per chip): expand every block
    from this shard's (hub-extended) expansion bitmap and accumulate
    the (P, vmax) mark matrix; the caller ships row d to part d with
    the packed all_to_all exchange.

    -> (marks (P, vmax) bool, edges () i32, ovf () bool)."""
    marks = None
    edges = jnp.zeros((), jnp.int32)
    ovf = jnp.zeros((), bool)
    for bi in range(len(blocks_data)):
        b = blocks_data[bi]
        blk = {"indptr": b["indptr"][0], "nbr": b["nbr"][0],
               "rank": b["rank"][0],
               "props": {n: v[0]
                         for n, v in b.get("props", {}).items()}}
        if "d_src" in b:
            for k in ("d_src", "d_dst", "d_rank", "d_valid", "d_tomb"):
                blk[k] = b[k][0]
            blk["d_props"] = {n: v[0]
                              for n, v in b.get("d_props", {}).items()}
        marks, total, ov = _block_marks(
            lambda f: f, blk, efbm, pid, EB, P, vmax, pred, pred_cols,
            hub_dense, acc=marks)
        ovf = ovf | ov
        edges = edges + total
    return marks, edges, ovf
