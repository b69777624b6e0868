"""The BFS level bodies, by need (ISSUE 13 satellite; PR 42).

One level of a frontier iteration, defined once for every layout of
tpu/bfs.py's two builders, which are its only callers (the
vertex-program engine, algo/engine.py, runs the flat edge-list form of
algo/graph.py and algo/kernels.py and calls nothing here):

  * `top_down_step`      — single-chip level body: expand every block
                           from the frontier bitmap and mark the far
                           ends in the (P, vmax) candidate bitmap (the
                           degenerate all_to_all: every part's slots
                           scatter into the one bitmap);
  * `bottom_up_step`     — single-chip direction-optimizing level body:
                           unvisited vertices scan their REVERSE
                           adjacency against the resident frontier
                           bitmap (no routing exchange at all);
  * `sharded_level_step` — the shard_map level body: expand + mark,
                           the caller exchanges marks over ICI; in
                           either direction where the caller chooses
                           one a level: bottom-up, the shard's
                           unvisited scan their reverse adjacency
                           against the WHOLE frontier bitmap, which the
                           caller gathered over the mesh, and the marks
                           take the same exchange.

All three are `_level_marks`.  Per block it lays the expansion out
(hop.py `_expand_plan`, which also gives the level's true size `total`
and its overflow flag) and then runs EVERY per-slot stage inside ONE
`_by_need` loop whose trip count is `ceil(min(max total, EB) / chunk)`:
`_expand_slots` for the trip's window (`rank[eidx]` and the predicate's
columns only where a predicate reads them), the live-tombstone test of
an armed delta plane, the predicate (`_keep`), bottom-up's membership
gather, and the mark scatter.  The loop carries the level's FLAT mark
bitmap (hop.py `_mark_flat`) and nothing else: no array of EB slots
leaves `_expand_plan`.  A level whose budget fits one chunk is the
straight-line program (`_by_need`'s static choice).  A level costs what
it expands, not what its edge budget holds.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..tpu import hop
from ..tpu.hop import (_by_need, _delta_cap, _delta_live,
                       _drop_live_tombstones, _expand_plan, _expand_slots,
                       _live_rows, _mark_flat, _part_view, _when,
                       take_halves)

__all__ = ["top_down_step", "bottom_up_step", "sharded_level_step",
           "level_trips", "delta_live", "LEVEL_CHUNK"]

# Slots of one part that one trip of a level's loop handles (`_by_need`);
# a level whose budget is no larger runs straight-line.  Not hop.py's
# CHUNK: the level loops carry one flat bitmap where `_traverse`'s carry
# budget-wide columns, and the chip reads them flat up to 2^13 where it
# read those flat up to 2^15.  Settled on the chip (PERF.md section 6,
# PR 42: the 30 M-edge BFS cell's six requests, both directions of its
# dense levels, mean device time of a five-level statement): 576.3 ms
# at 2^13, 577.0 at 2^12, 580.0 at 2^11, 596.5 at 2^10, 632.7 at 2^9;
# 715.2 at 2^14, 812.3 at 2^15, 845.7 at 2^16 (what grows past 2^13 is
# the row-offset gather of a bottom-up trip).  The largest trip that
# was flat.
LEVEL_CHUNK = 1 << 13


def _keep(block, src, dst, rk, eidx, ve, pred, pred_cols,
          swap_ends: bool = False):
    """The compiled edge predicate over one part's expanded slots,
    folded into the valid mask.  `swap_ends` is the bottom-up contract:
    $^/$$ are TRAVERSAL source/destination, and bottom-up expands the
    REVERSE adjacency, so the expansion source is the traversal
    DESTINATION (the newly reached vertex) and the neighbor is the
    frontier side — the endpoint columns the predicate sees are
    swapped.  `rk` is None unless the predicate reads `_rank`."""
    if pred is None:
        return ve
    ps, pd = (dst, src) if swap_ends else (src, dst)
    cols = {"_rank": rk, "_src": ps, "_dst": pd}
    for name in pred_cols:
        if not name.startswith("_"):
            cols[name] = take_halves(block["props"][name], eidx)
    return pred(cols) & ve


def delta_live(blocks_data):
    """Whether any block's armed delta plane holds a row or a
    tombstone (a traced scalar; False where no plane is armed)."""
    live = jnp.zeros((), bool)
    for b in blocks_data:
        if _delta_cap(b):
            tomb, rows = _delta_live(b)
            live = live | tomb | rows
    return live


def _level_marks(over, blocks, pid, efbm, EB: int, P: int, vmax: int,
                 pred, pred_cols, hub_dense, chunk: int, member_of=None,
                 either=None):
    """One level over every block: the flat (P * vmax,) bitmap of the
    vertices it reaches.  `over` maps a per-part function over the
    layout's leading axes as in hop.py's `_traverse` (`jax.vmap` on one
    chip, the identity inside a shard); each block holds `indptr`,
    `nbr`, `rank`, `props` and, armed, the delta plane's `d_*` leaves.

    Top-down, a kept slot marks its far end.  With `member_of` (the
    resident (P, vmax) frontier bitmap) the level is BOTTOM-UP: the
    blocks are the reverse adjacency, `efbm` the unvisited, and a slot
    marks its SOURCE, routed to its owner row (a degree-split hub row's
    source belongs to another part), where its neighbour is a member.

    With `either` = (`go_up`, the blocks' reverse twins, the unvisited)
    the direction is the traced scalar `go_up`, and `member_of` serves
    the bottom-up side alone.  The plan is laid out ONCE, from the row
    offsets and the bitmap of the direction taken (two selects a vertex
    wide), and a `lax.cond` holds the two `_by_need` loops and nothing
    else: a plan's running sums over a budget of 50 M slots are what a
    level takes longest to compile (one `reduce-window` of the running
    maximum, 54 of 59 s once it sat inside a branch), and they are the
    same work in either direction.  Every budget so run must loop.

    An armed delta plane is merged BY WHAT IT HOLDS (ISSUE 19; the
    stages of `_traverse`): tombstoned base slots dropped inside the
    loop, the plane's appended rows marked after it, each behind the
    plane's live count, so that an empty plane costs a level what no
    plane costs.  Bottom-up never sees a plane (bfs.py keeps a level
    top-down while one holds anything, on any shard).

    -> (marks, edges, ovf, trips run, trips budgeted), all but `marks`
    with the leading axes: the trip counts are `_by_need`'s summed over
    the blocks (0 where no loop was emitted), the same for every part
    under a vmap, which runs a loop to its fullest part's count."""
    want_rank = pred is not None and "_rank" in pred_cols
    marks = jnp.zeros((P * vmax,), bool)
    edges = ovf = None
    run = budget = 0
    for i, b in enumerate(blocks):
        dcap = _delta_cap(b)
        has_tomb, has_rows = _delta_live(b) if dcap else (None, None)
        if either is None:
            laid = b, efbm
        else:
            go_up, twins, eunvis = either
            laid = ({"indptr": jnp.where(go_up, twins[i]["indptr"],
                                         b["indptr"])},
                    jnp.where(go_up, eunvis, efbm))
        total, ov, plan, _, _ = _expand_plan(over, laid[0], pid, laid[1],
                                             EB, hop.PLAN_CHUNK)

        def window_of(blk, bottom_up: bool):
            def window(outs, lo, size):
                src, dst, rk, eidx, ve = over(
                    lambda bk, pd, pl, tot: _expand_slots(
                        bk["nbr"], bk["rank"] if want_rank else None, pl,
                        tot, lo, size, EB, P, pd, vmax, hub_dense))(
                    blk, pid, plan, total)
                if _delta_cap(blk):
                    ve = _drop_live_tombstones(over, blk, pid, eidx, ve,
                                               has_tomb)
                keep = ve if pred is None else over(
                    lambda bk, _p, *a: _keep(bk, *a, pred, pred_cols,
                                             bottom_up))(
                    blk, pid, src, dst, rk, eidx, ve)
                if bottom_up:
                    nb = jnp.where(keep, dst, 0)
                    keep = keep & member_of[nb % P, nb // P]
                return (_mark_flat(outs[0], src if bottom_up else dst,
                                   keep, P, vmax),)
            return window

        # the live slots of the fullest part: the loop's trip count
        n = jnp.minimum(jnp.max(total), EB)
        if either is None:
            (marks,), r, bd = _by_need(
                window_of(b, member_of is not None), (marks,), n, EB,
                chunk=chunk)
        else:
            bd = EB // chunk
            assert EB > chunk and not EB % chunk, (EB, chunk)
            (marks,), r = jax.lax.cond(
                go_up,
                lambda m: _by_need(window_of(twins[i], True), (m,), n, EB,
                                   chunk=chunk)[:2],
                lambda m: _by_need(window_of(b, False), (m,), n, EB,
                                   chunk=chunk)[:2], marks)
        run, budget = run + r, budget + bd
        if dcap:
            _s, tdst, _r, tkeep, tact = _live_rows(
                over, b, pid, efbm, P, has_rows, b["rank"].dtype, pred,
                [c for c in pred_cols if not c.startswith("_")])
            marks = _when(
                has_rows, lambda m, d, k: _mark_flat(m, d, k, P, vmax),
                lambda m, d, k: m, marks, tdst, tkeep)
            total = total + jnp.sum(tact, axis=-1, dtype=jnp.int32)
        edges = total if edges is None else edges + total
        ovf = ov if ovf is None else ovf | ov
    zero = jnp.zeros_like(edges)
    return marks, edges, ovf, zero + run, zero + budget


def top_down_step(blocks_data, efbm, EB: int, P: int, vmax: int, pids,
                  pred=None, pred_cols=(), hub_dense=None,
                  chunk: int = LEVEL_CHUNK):
    """Single-chip level body, forward direction: expand every block
    from the (possibly hub-extended) frontier bitmap `efbm` and mark
    the destinations in the one (P, vmax) ownership bitmap (every leaf
    of a block, the delta plane's among them, has a leading part axis).

    -> (cand (P, vmax) bool, edges (P,) i32, ovf (P,) bool, trips run
    and budgeted (P,) i32)."""
    marks, *rest = _level_marks(jax.vmap, blocks_data, pids, efbm, EB, P,
                                vmax, pred, pred_cols, hub_dense, chunk)
    return (marks.reshape(P, vmax), *rest)


def _reverse(blocks):
    """Each block's reverse-direction twin (its `rev_*` leaves) as a
    block of its own."""
    return [{"indptr": b["rev_indptr"], "nbr": b["rev_nbr"],
             "rank": b["rev_rank"], "props": b.get("rev_props", {})}
            for b in blocks]


def bottom_up_step(blocks_data, fbm, eunvis, EB: int, P: int,
                   vmax: int, pids, pred=None, pred_cols=(),
                   hub_dense=None, chunk: int = LEVEL_CHUNK):
    """Single-chip direction-optimizing level body: expand the REVERSE
    adjacency (each block's `rev_*` leaves) of unvisited vertices
    (`eunvis`, hub-extended by the caller); a vertex joins the frontier
    if any in-neighbor's bit is set in the resident frontier bitmap
    `fbm`.  Needs NO routing exchange: each owner decides its own
    vertices from the global bitmap.  It expands every in-edge of every
    unvisited vertex, which is what `edges` counts.

    -> as `top_down_step`."""
    marks, *rest = _level_marks(jax.vmap, _reverse(blocks_data), pids,
                                eunvis, EB, P, vmax, pred, pred_cols,
                                hub_dense, chunk, member_of=fbm)
    return (marks.reshape(P, vmax), *rest)


def sharded_level_step(blocks_data, efbm, EB: int, P: int, pid,
                       vmax: int, pred=None, pred_cols=(),
                       hub_dense=None, chunk: int = LEVEL_CHUNK,
                       up=None):
    """shard_map level body (one part per chip): expand every block
    from this shard's (hub-extended) expansion bitmap into the shard's
    (P, vmax) mark matrix; the caller ships row d to part d with the
    packed all_to_all exchange.

    With `up` = (`go_up`, `eunvis`, `whole_fbm`) the level takes the
    direction the traced scalar `go_up` says, the same on every shard.
    Bottom-up, this shard's (hub-extended) unvisited `eunvis` scan the
    shard's REVERSE adjacency (each block's `rev_*` leaves) against
    `whole_fbm`, the (P, vmax) frontier bitmap of every part (the caller
    gathers it over the mesh: a shard holds its own row alone).  A kept
    slot marks its source in the owner's row of the mark matrix, which
    is this shard's but for a degree-split hub row's, so the rows take
    the same exchange.  The level's budget must loop.

    -> (marks (P, vmax) bool, edges () i32, ovf () bool, trips run and
    budgeted () i32)."""
    part = _part_view(blocks_data)
    member_of = either = None
    if up is not None:
        go_up, eunvis, member_of = up
        either = go_up, _reverse(part), eunvis
    marks, *rest = _level_marks(lambda f: f, part, pid, efbm, EB, P, vmax,
                                pred, pred_cols, hub_dense, chunk,
                                member_of=member_of, either=either)
    return (marks.reshape(P, vmax), *rest)


def level_trips(indptrs, efbm, EB: int, chunk: int):
    """The trips of `chunk` slots that one part's level loops run to
    expand `efbm` (a frontier, or the unvisited) over the blocks whose
    row offsets are `indptrs`: `_by_need`'s count a block, summed, from
    one masked sum of row-offset differences a block and no plan.  A
    slot of the bitmap that is no vertex (a shard's padding) has degree
    0 and drops out.  -> () i32."""
    trips = jnp.zeros((), jnp.int32)
    for ip in indptrs:
        need = jnp.sum(jnp.where(efbm, ip[1:] - ip[:-1], 0),
                       dtype=jnp.int32)
        trips = trips + (jnp.minimum(need, EB) + (chunk - 1)) // chunk
    return trips
