"""Device BFS: the program behind FIND SHORTEST PATH.

Level-synchronous over the pinned CSR with a bitmap frontier: a level
expands the frontier's out-edges, marks the far ends in a per-owner
bitmap and keeps those `dist` has not seen (`new = cand & (dist < 0)`),
so there is no sort and no frontier overflow; the edge budget of a level
is the only size that escalates, and it bounds a level's SHAPES, not its
work.  The level bodies live in algo/frontier.py and run by need: every
per-slot stage of a level sits in one device loop whose trip count is
what the level expands (hop.py `_by_need`), and a level whose budget
fits one chunk is the straight-line program.  This module composes them
with the `dist` update and, with the reverse blocks at hand, the
per-level switch to the bottom-up body: on one chip by the frontier's
share of the unvisited (`build_bfs_fn_local`), on a mesh by the trips
either direction would run on its fullest part (`build_bfs_fn`).

Both builders return `dist` (the depth of every vertex, -1 unreached),
`hop_edges` (slots each level really expanded, a part), `ovf_expand`,
`bottom_up` (the direction each level took) and `chunks_run` /
`chunks_budget` (the trips each level's loops ran and the trips its
budget holds, a part; 0 where no loop was emitted), and carry the
trip's size as `fn.chunk`.  The host walks predecessors back from the
target (tpu/paths.py)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..algo.frontier import (LEVEL_CHUNK, bottom_up_step, delta_live,
                             level_trips, sharded_level_step,
                             top_down_step)
from .hop import (_exchange_marks, _extend_fbm_local,
                  _extend_fbm_sharded, _hub_consts, _norm_ebs, _pack_bits,
                  _part_view, _unpack_or, a2a_payload_bytes)


def bfs_exchange_bytes(P: int, vmax: int, max_steps: int,
                       lanes: int = 1) -> int:
    """Total bit-packed all_to_all payload of one sharded BFS run: BFS
    exchanges EVERY level (the final level's received candidates still
    update dist), unlike the traverse kernels which skip the last hop's
    exchange, and whichever way a level went (a bottom-up level's
    marks take it too).  This is the number `tpu_all_to_all_bytes`
    grows by per run — the runtime accounts it analytically because
    the exchange is fused inside the jitted program (no host-visible
    boundary to measure).  The `all_gather` before a level that
    chooses its direction is not in it (`bfs_gather_bytes`).  Zero on
    a 1-part mesh."""
    return max_steps * a2a_payload_bytes(P, vmax, lanes)


def bfs_gather_bytes(P: int, vmax: int, levels: int) -> int:
    """Total bit-packed all_gather payload of one sharded BFS run whose
    program chooses the direction of `levels` of its levels: before each
    of them every part ships its own row of the frontier bitmap to every
    part, reckoned as the exchange's rows are (`a2a_payload_bytes`: the
    sum of every device's send payload).  This is what
    `tpu_bfs_gather_bytes` grows by per run, a series of its own and no
    share of `tpu_bfs_exchange_bytes`.  Zero on a 1-part mesh."""
    return levels * a2a_payload_bytes(P, vmax)


# What one trip of a bottom-up level costs a shard, in trips of a
# top-down level: the weight on bottom-up's side of the per-level choice
# (`build_bfs_fn`).  A bottom-up slot adds the membership gather out of
# the whole (P, vmax) frontier bitmap to a top-down slot's two gathers
# and one scatter.  Read on the chip (PERF.md section 6, PR 45: the
# four-chip BFS cell's traced run, a least-squares fit of 23 statements'
# device time on the slots their fullest part expanded either way):
# 48.73 ns a slot bottom-up, 40.55 top-down, 1.20 (PR 42's one-chip fit
# read 49.7 and 41.2, 1.21); rounded up to the quarter, to top-down's
# side, whose count the frontier gives exactly.
BOTTOM_UP_TRIP_COST = 1.25


def _whole_frontier(fbm, vmax: int):
    """Every part's row of the frontier bitmap on every shard, (P, vmax)
    bool: ONE bit-packed all_gather over 'part' (`_exchange_marks`'
    packing), ceil(vmax/32) words a part."""
    with jax.named_scope("hop/exchange"):
        words = jax.lax.all_gather(_pack_bits(fbm[None]), "part")
        return _unpack_or(words, vmax)


def build_bfs_fn(mesh, P: int, EB, max_steps: int, vmax: int,
                 pred=None, pred_cols=(), have_rev: bool = False,
                 hub_dense=None, chunk: int = LEVEL_CHUNK):
    """Sharded BFS program: (blocks_data, frontier) →
    {dist (P, vmax), ovf_expand, hop_edges (P, steps),
    chunks_run, chunks_budget (P, steps): each shard's own trips,
    bottom_up (steps,) bool: the direction the shards agreed on, level
    for level}.

    frontier: (P, vmax) bool seed bitmap.  pred/pred_cols: optional
    compiled edge predicate (exprjit) — a filtered FIND SHORTEST PATH
    only traverses mask-passing edges, matching the host oracle's
    per-expansion filter.

    With `have_rev` (every block carries its reverse twin's "rev_*"
    leaves, sharded like the rest) a level CHOOSES ITS DIRECTION: it
    goes bottom-up (every shard's unvisited scan their in-edges against
    the whole frontier bitmap) where that takes fewer trips of the level
    loop than expanding the frontier's out-edges, a bottom-up trip
    weighed by `BOTTOM_UP_TRIP_COST`; a tie stays top-down.  The trips
    are the FULLEST part's on either side (a masked sum of row-offset
    differences a shard, then a `pmax`): every chip waits at the
    exchange for the one whose loop runs longest.  Every collective of
    a level sits outside the `lax.cond`, so neither branch holds one:
    the hub `psum`s, the `all_gather` of the frontier bitmap
    (`bfs_gather_bytes`), the one `pmax` that settles the choice, and
    the `all_to_all` after the level, taken or not (a degree-split hub
    row's source is another part's).  An armed delta plane keeps a
    level top-down while ANY shard's plane holds anything (one shard's
    reverse adjacency does not hold the rows another's plane took); an
    armed, empty plane changes no level.  A level whose budget the loop
    does not chunk (at most one `chunk`) is emitted top-down alone:
    there is no trip for the other direction to save, no second branch
    to compile and no `all_gather`.  `fn.gather_levels` is the number of
    levels that have the choice.

    Mesh contract (PR 17): in_specs name only the 'part' axis, so the
    same program runs on the legacy 1-D ('part',) mesh and on the
    2-axis ('lane', 'part') grid (CSR + dist replicated over the lane
    rows); the per-level exchange payload is bfs_exchange_bytes.
    chunk: the level loops' trip size, in slots of one part
    (frontier.py's `LEVEL_CHUNK` everywhere but in tests)."""

    ebs = _norm_ebs(EB, max_steps, False)
    hubs_c, hub_owner, hub_local = _hub_consts(hub_dense, P)
    # the levels whose loop `_by_need` emits: the ones with trips to save
    chooses = [have_rev and e > chunk and not e % chunk for e in ebs]

    def kernel(blocks_data, frontier):
        fbm = frontier[0]                       # (vmax,) bool seeds
        pid = jax.lax.axis_index("part").astype(jnp.int32)
        dist = jnp.where(fbm, 0, -1).astype(jnp.int32)
        ovf_e = jnp.zeros((), bool)
        hop_edges, went_bu, runs, budgets = [], [], [], []
        part = _part_view(blocks_data)

        def ext(x):
            if hubs_c is None:
                return x
            return _extend_fbm_sharded(x, pid, hub_owner, hub_local)

        for level in range(1, max_steps + 1):
            EBl = ebs[level - 1]
            efbm = ext(fbm)
            if chooses[level - 1]:
                eunvis = ext(dist < 0)
                whole = _whole_frontier(fbm, vmax)
                # both directions' trips on this shard, and whether its
                # plane holds anything: the fullest part's, by ONE pmax
                td, bu, live = jax.lax.pmax(jnp.stack([
                    level_trips([b["indptr"] for b in part], efbm, EBl,
                                chunk),
                    level_trips([b["rev_indptr"] for b in part], eunvis,
                                EBl, chunk),
                    delta_live(blocks_data).astype(jnp.int32)]), "part")
                use_bu = (bu * BOTTOM_UP_TRIP_COST < td) & (live == 0)
                up = use_bu, eunvis, whole
            else:
                use_bu, up = jnp.zeros((), bool), None
            marks, edges, ovf, run, budget = sharded_level_step(
                blocks_data, efbm, EBl, P, pid, vmax, pred=pred,
                pred_cols=pred_cols, hub_dense=hubs_c, chunk=chunk, up=up)
            went_bu.append(use_bu)
            ovf_e = ovf_e | ovf
            hop_edges.append(edges)
            runs.append(run)
            budgets.append(budget)
            cand = _exchange_marks(marks, P, vmax)
            new = cand & (dist < 0)
            dist = jnp.where(new, level, dist)
            fbm = new

        return {"dist": dist[None],
                "hop_edges": jnp.stack(hop_edges)[None],
                "chunks_run": jnp.stack(runs)[None],
                "chunks_budget": jnp.stack(budgets)[None],
                "ovf_expand": ovf_e[None],
                "bottom_up": jnp.stack(went_bu)}

    from jax.sharding import PartitionSpec

    from .device import shard_map as _shard_map
    spec = PartitionSpec("part")
    # the flags are the same on every shard: they leave as one copy
    out_specs = dict.fromkeys(("dist", "hop_edges", "chunks_run",
                               "chunks_budget", "ovf_expand"), spec)
    out_specs["bottom_up"] = PartitionSpec()
    fn = jax.jit(_shard_map(kernel, mesh=mesh, in_specs=(spec, spec),
                            out_specs=out_specs))
    fn.chunk = chunk
    fn.gather_levels = sum(chooses)
    return fn


def build_bfs_fn_local(P: int, EB, max_steps: int, vmax: int,
                       pred=None, pred_cols=(), have_rev: bool = False,
                       n_phantom: int = 0, hub_dense=None,
                       chunk: int = LEVEL_CHUNK):
    """Single-chip variant (vmap over parts; every part's slots mark into
    the one candidate bitmap, the degenerate all_to_all).

    With `have_rev` (blocks_data carries each block's REVERSE-direction
    twin under "rev_*" keys) the kernel is DIRECTION-OPTIMIZING: on
    dense levels it switches bottom-up — every still-unvisited vertex
    scans its reverse-adjacency and joins the next frontier if any
    in-neighbor's bit is set in the (single-chip-resident) frontier
    bitmap.  Bottom-up needs NO routing exchange at all: each owner
    decides its own vertices from the global bitmap, which is exactly
    what the bitmap-frontier currency makes cheap.  Both branches share
    the level body via lax.cond; the classic switch heuristic
    (frontier edges vs unvisited edges, Beamer-style) degrades to a
    frontier-population threshold since degrees are already summed by
    the expansion itself.  `bottom_up` (steps,) says which way each
    level went (all false without `have_rev`); `chunks_run` and
    `chunks_budget` (P, steps) hold the trips of the branch it took, the
    same on every part (a vmapped loop runs to its fullest part's
    count)."""
    pids = jnp.arange(P, dtype=jnp.int32)
    ebs = _norm_ebs(EB, max_steps, False)
    hubs_c, hub_owner, hub_local = _hub_consts(hub_dense, P)

    def ext(x):
        if hubs_c is None:
            return x
        return _extend_fbm_local(x, hub_owner, hub_local, P)

    def fn(blocks_data, frontier):
        fbm = frontier                          # (P, vmax) bool seeds
        armed = any("d_src" in b for b in blocks_data)
        dist = jnp.where(fbm, 0, -1).astype(jnp.int32)   # (P, vmax)
        ovf_e = jnp.zeros((P,), bool)
        hop_edges, went_bu, runs, budgets = [], [], [], []

        def top_down(blocks, f, EBl):
            return top_down_step(blocks, ext(f), EBl, P, vmax, pids,
                                 pred=pred, pred_cols=pred_cols,
                                 hub_dense=hubs_c, chunk=chunk)

        def bottom_up(blocks, f, unvis, EBl):
            return bottom_up_step(blocks, f, ext(unvis), EBl, P, vmax,
                                  pids, pred=pred, pred_cols=pred_cols,
                                  hub_dense=hubs_c, chunk=chunk)

        for level in range(1, max_steps + 1):
            EBl = ebs[level - 1]
            if have_rev:
                unvis = dist < 0
                # dense-level switch: frontier holds a meaningful share
                # of the unvisited set → scanning unvisited in-edges
                # beats expanding frontier out-edges.  Padding slots of
                # smaller partitions sit forever in `unvis`; subtract
                # them so skewed layouts don't suppress the switch.
                use_bu = fbm.sum() * 8 > unvis.sum() - n_phantom
                if armed:
                    # the reverse adjacency has no delta: a level goes
                    # top-down while the plane holds anything, and an
                    # armed plane that holds nothing changes no level
                    use_bu = use_bu & ~delta_live(blocks_data)
                cand, edges, ovf, run, budget = jax.lax.cond(
                    use_bu,
                    lambda args: bottom_up(blocks_data, args[0], args[1],
                                           EBl),
                    lambda args: top_down(blocks_data, args[0], EBl),
                    (fbm, unvis))
            else:
                use_bu = jnp.zeros((), bool)
                cand, edges, ovf, run, budget = top_down(
                    blocks_data, fbm, EBl)
            went_bu.append(use_bu)
            ovf_e = ovf_e | ovf
            hop_edges.append(edges)
            runs.append(run)
            budgets.append(budget)
            new = cand & (dist < 0)
            dist = jnp.where(new, level, dist)
            fbm = new

        return {"dist": dist, "hop_edges": jnp.stack(hop_edges, axis=1),
                "chunks_run": jnp.stack(runs, axis=1),
                "chunks_budget": jnp.stack(budgets, axis=1),
                "ovf_expand": ovf_e, "bottom_up": jnp.stack(went_bu)}

    fn = jax.jit(fn)
    fn.chunk = chunk
    return fn
