"""The sharded multi-hop traversal kernel — bitmap-frontier design.

One `shard_map` program runs the WHOLE N-step GO expansion on device:
per hop, each chip expands its shard of the frontier through its local
CSR block(s) (a vectorized segment gather — the MXU/VPU replacement for
the reference's per-vid RocksDB prefix loops in GetNeighborsProcessor),
applies the compiled predicate mask, and marks destination vertices in a
per-owner **bitmap** that is exchanged with ONE bool `lax.all_to_all`
over ICI — replacing the reference's per-hop storage.thrift fan-out
(StorageClient::getNeighbors; reference: src/clients/storage,
src/storage/query [UNVERIFIED — empty mount, SURVEY §0]).

Why a bitmap (round-4 redesign, VERDICT r3 item 3): the previous design
kept the frontier as a padded (P, F) sorted id list, which cost three
O(EB log EB) sorts per hop (sort-unique dedup, stable argsort routing,
merge sort) — sort-heavy work on sort-weak hardware for an expansion
whose useful work is an int32 gather.  The frontier is now a
(P, vmax) bool membership bitmap sharded by vid ownership
(dense % P — the vid-hash partition map), which makes all three sorts
disappear structurally:

  * dedup      = the scatter-max mark itself (duplicate dsts set the
                 same bit);
  * routing    = the bitmap's layout (row d of the mark matrix IS the
                 bucket for part d — no argsort, no bucket overflow);
  * merge      = a bool OR-reduce over the received rows;
  * the F bucket, its escalation rung, and the ovf_route/ovf_frontier
    flags cease to exist — the only dynamic budget left is EB.

Per hop the work is O(EB) gathers/scatters + an O(vmax) cumsum, versus
O(EB log EB) before; the exchange payload is P*vmax bools versus
P*F int32 words (at north-star shape: 1 MB versus 64 MB).

Static-shape policy (SURVEY §7 hard-part #1): the per-block edge budget
EB is a power-of-two bucket chosen by the runtime; every kernel output
carries overflow flags, and the runtime re-runs with doubled buckets on
overflow (inputs are never consumed, so the retry is exact).

Work by need (PR 25): EB bounds a hop's SHAPES, not its work.  A gather
or scatter on the chip costs what its slots cost (20 to 27 ns apiece,
PERF.md section 5), so every per-slot stage of a hop runs over
ceil(need / CHUNK) chunks inside one device loop whose trip count is
the expansion's own size (`_by_need`).  A hop whose budget fits one chunk
compiles to the straight-line program.  The BFS level bodies
(algo/frontier.py) run the same way since PR 42: one loop a level and
block, whose carry is the level's mark bitmap (`_mark_flat`).  Since
PR 29 the expansion PLAN follows need too (`_expand_plan`): over a bitmap wider than PLAN_CHUNK
its scatters are sized by the words of the bitmap that hold an
expanding vertex (one update a word to list them, PLAN_BLOCK updates a
listed word, by need), not by the part's local vertices; what still
runs over the whole bitmap or the whole budget is streaming (`where`,
cumsum, running maximum, reduce, pad, reshape, the bit pack and
unpack).

Frontier representation between hops: (P, vmax) bool, row p = the
membership bitmap of part p's local ids (dense id = local * P + p).
Expansion enumerates set bits in ascending local-id order, so captured
edge slots stay (part, src)-contiguous ascending-eidx — the invariant
the host materializers rely on.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .device import shard_map as _shard_map

MAXI = np.iinfo(np.int32).max


def _stage(name: str):
    """Trace the function's operations under `jax.named_scope(name)`:
    their op_name then carries `hop/<stage>`, a name that stays put
    when the HLO text of a fusion does not (a profiler trace keys
    device time by it)."""
    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kw):
            with jax.named_scope(name):
                return fn(*args, **kw)
        return scoped
    return wrap


# Slots of one part that one trip of a by-need loop handles (_by_need).
# A hop whose budget is no larger runs straight-line.  Settled on the
# chip (PERF.md, PR 25): in the 30 M-edge cell device time is flat from
# 2^13 to 2^15 (within 1%), 2% more at 2^16, 4% at 2^17, a third at 2^20.
CHUNK = 1 << 14


def _window(x, lo, size: int):
    """Slots [lo, lo + size) of x's last axis — x itself when that is
    all of them (the straight-line case slices nothing)."""
    if size == x.shape[-1]:
        return x
    return jax.lax.dynamic_slice_in_dim(x, lo, size, axis=-1)


def _put(out, x, lo):
    """A chunk's values written at slot `lo` of the preallocated `out`
    — x itself when it already spans all of out."""
    if x.shape == out.shape:
        return x
    return jax.lax.dynamic_update_slice_in_dim(out, x, lo, axis=-1)


def _when(live, on, off, *ops):
    """`lax.cond(live, on, off, *ops)` for a stage that runs only where
    the delta plane holds something: `live` is a traced scalar taken
    OUTSIDE every vmap (under one a `cond` becomes a `select` and both
    sides run).  Inside a shard_map what a branch computes from the
    shard's data varies over the mesh and a constant does not, and the
    two branches must agree: each result is cast to the union (a
    shard's `live` varies there; elsewhere the plain `cond` does)."""
    if not jax.typeof(live).vma:
        return jax.lax.cond(live, on, off, *ops)
    want = [jax.eval_shape(f, *ops) for f in (on, off)]
    vma = jax.tree.map(lambda a, b: (a.vma or frozenset()) | (b.vma or frozenset()),
                       *want)

    def cast(f):
        def run(*a):
            return jax.tree.map(
                lambda o, v: jax.lax.pcast(
                    o, tuple(v - jax.typeof(o).vma), to="varying")
                if v - jax.typeof(o).vma else o, f(*a), vma)
        return run
    return jax.lax.cond(live, cast(on), cast(off), *ops)


def _by_need(body, outs, n, width: int, tail: int = 0, chunk: int = CHUNK,
             tail_live=None):
    """Run a per-slot stage of a hop over the slots the expansion
    FILLED, not over the hop's whole edge budget.

    body(outs, lo, size) -> outs handles slots [lo, lo + size) of the
    last axis and writes what it produced into the preallocated `outs`
    (a tuple of arrays holding the fill values slots never visited
    keep).  `n` is the traced number of live slots among the first
    `width`; the body runs on [c*chunk, (c+1)*chunk) for
    c < ceil(n / chunk) inside one device loop, then on the `tail`
    slots that follow `width` (the delta plane's appended rows) where
    the traced scalar `tail_live` says they hold something: an armed
    plane with nothing in it runs no trip for them.  A gather's cost on
    the chip follows the slots it is asked for, so a hop that filled a
    fifth of its budget pays a fifth.

    The choice between loop and no loop is the STATIC width: a budget
    of one chunk or less (or one that chunks do not tile) runs the body
    once over the budget, which is the straight-line program.  Under vmap
    the loop runs to the largest trip count among the mapped instances.

    Returns (outs, chunks run, chunks budgeted) — (outs, 0, 0) when no
    loop was emitted."""
    def with_tail(o):
        if not tail:
            return o
        return _when(tail_live, lambda x: body(x, width, tail),
                     lambda x: x, o)

    if width <= chunk or width % chunk:
        return with_tail(body(outs, 0, width)), 0, 0
    trips = (jnp.minimum(n, width) + (chunk - 1)) // chunk
    # inside a shard_map a fresh constant is the same on every shard and
    # what the body writes is not: the loop carry takes the body's type
    want = jax.eval_shape(lambda o: body(o, 0, chunk), outs)
    missing = [tuple((w.vma or frozenset()) - jax.typeof(o).vma)
               for o, w in zip(outs, want)]
    outs = tuple(jax.lax.pcast(o, axes, to="varying") if axes else o
                 for o, axes in zip(outs, missing))
    outs = jax.lax.fori_loop(
        0, trips, lambda c, o: body(o, c * chunk, chunk), outs)
    return with_tail(outs), trips, width // chunk


# Lane updates one trip of the member plan's loop issues for one part
# (_plan_members), and the bitmap width at or under which a hop compiles
# the whole-bitmap plan instead (_expand_plan): a bitmap no wider than
# one trip has nothing for the loop to skip.  Settled on the chip
# (PERF.md, PR 29): at 1,500,000 local vertices and budgets up to 2^18 a
# 3-hop program is flat from 2^10 to 2^14 (31.80 to 31.93 ms); with every
# word of 8 x 125,000 vertices listed and a 2^22 budget the plan alone
# takes 12.0 ms at 2^14 and 20.7 at 2^12 against the whole-bitmap plan's
# 18.4 (XLA sorts a trip's indices before a scatter into an operand that
# large, about 0.4 ms a trip), so the largest trip that was flat.
PLAN_CHUNK = 1 << 14
# Local ids that share one entry of the member plan's word list: a word
# that holds a member costs PLAN_BLOCK lane updates, a list entry one.
# 31.8 ms at 32, 33.6 at 16, 31.4 at 64 (the same program; PR 29).
PLAN_BLOCK = 32


def _frontier_starts(indptr, fbm):
    """(deg, starts, total) of a frontier bitmap over its CSR rows, along
    the last axis: each vertex's expansion size (0 off the frontier), its
    first slot, and the expansion's size."""
    deg = jnp.where(fbm, indptr[..., 1:] - indptr[..., :-1],
                    0).astype(jnp.int32)
    ends = jnp.cumsum(deg, axis=-1)
    return deg, ends - deg, ends[..., -1]


def _row_offsets(indptr, starts):
    """The one per-vertex table the per-slot half reads: slot j of a
    frontier vertex `row` holds edge `indptr[row] + (j - starts[row])`,
    and both terms are known before the slots are walked, so their
    difference is one streaming pass here and ONE gather a slot there
    (`_expand_slots`: `off[row] + j`), integer for integer the same
    index.  `starts` carries the plan's leading axes, which `indptr`
    may lack (a shard's CSR under its lanes)."""
    return indptr[..., :-1] - starts


def _plan_whole(indptr, fbm, EB: int):
    """One part's expansion plan by two scatters over EVERY local
    vertex, whatever the frontier holds: the plan of a bitmap no wider
    than PLAN_CHUNK (`_expand_plan`).

    Slot→source-row assignment is a cumsum-scatter, not a binary
    search: bump +1 at each frontier vertex's first slot, prefix-sum
    over the EB slots, then map the compact row number back to a local
    id through a scattered lookup table — O(vmax + EB) total, versus
    O(EB log vmax) for searchsorted (the log factor dominated the old
    kernel's per-slot cost on both the VPU and the CPU-emulated mesh).
    """
    vmax = fbm.shape[0]
    with jax.named_scope("hop/expand"):
        deg, starts, total = _frontier_starts(indptr, fbm)
        has = deg > 0
        # compact index of each expanding vertex, and its inverse table
        cidx = jnp.cumsum(has.astype(jnp.int32)) - 1
        vid_of = jnp.zeros((vmax,), jnp.int32).at[
            jnp.where(has, cidx, vmax)].set(
            jnp.arange(vmax, dtype=jnp.int32), mode="drop")
        # +1 at each expanding vertex's first slot; prefix-sum = compact
        # row
        bump = jnp.zeros((EB,), jnp.int32).at[
            jnp.where(has, starts, EB)].add(1, mode="drop")
        crow = jnp.cumsum(bump) - 1               # (EB,)
    return total, total > EB, (vid_of, _row_offsets(indptr, starts), crow)


@_stage("hop/expand")
def _plan_members(indptr, fbm, EB: int, chunk: int):
    """The expansion plan laid out from the frontier's expanding
    MEMBERS: no gather or scatter here has one index per local vertex.

    A member is a frontier vertex with an edge whose first slot lies
    below EB (a later one owns no slot of this hop; `total > EB` is the
    overflow the ladder retries).  Local ids are taken PLAN_BLOCK to a
    word.  Two levels: one scatter with an update per WORD lists the
    words that hold a member, ascending; a by-need loop over that list
    (`_by_need`, `chunk` lane updates a trip and part) fetches each
    listed word's lanes as one row and writes every member's local id
    + 1 at its first slot.  A running maximum over the EB slots then IS
    the slot→source-row table (members ascend with their first slots),
    so there is neither a compact-row table nor a per-slot gather
    through one.

    Arrays carry the builder's leading axes (`indptr` may lack them: a
    shard's CSR under its lanes); the scatters run on flat operands,
    every row at its own offset, as `_compact_cap`'s do.  Whole-width
    passes that stay are streaming: the `where`s, the cumsums, a
    reduce, the pad and reshape.

    Returns (total, ovf, plan, updates issued, updates the whole-bitmap
    plan issues) — the counts per part.
    """
    B = PLAN_BLOCK
    vmax = fbm.shape[-1]
    lead = fbm.shape[:-1]
    rows = int(np.prod(lead, dtype=np.int64))
    W = -(-vmax // B)                         # words a part
    CW = max(chunk // B, 1)                   # list entries a trip
    LW = -(-min(W, EB) // CW) * CW            # the list, in whole trips
    deg, starts, total = _frontier_starts(indptr, fbm)
    # a member's first slot; EB (dropped by every scatter) elsewhere
    at = jnp.where((deg > 0) & (starts < EB), starts, EB)
    at = jnp.pad(at, [(0, 0)] * len(lead) + [(0, W * B - vmax)],
                 constant_values=EB).reshape(lead + (W, B))
    full = jnp.any(at < EB, axis=-1)          # words holding a member
    widx = jnp.cumsum(full, axis=-1, dtype=jnp.int32) - 1
    nwords = widx[..., -1] + 1                # lead
    row0 = jnp.arange(rows, dtype=jnp.int32).reshape(lead + (1,))
    words = jnp.zeros((rows * LW,), jnp.int32).at[
        jnp.where(full, widx + row0 * LW, rows * LW).reshape(-1)].set(
        jnp.broadcast_to(jnp.arange(W, dtype=jnp.int32),
                         full.shape).reshape(-1),
        mode="drop").reshape(lead + (LW,))
    lanes = at.reshape(rows * W, B)
    lane = jnp.arange(B, dtype=jnp.int32)

    def mark_firsts(outs, lo, size):
        w = _window(words, lo, size)          # lead + (size,)
        live = lo + jnp.arange(size, dtype=jnp.int32) < nwords[..., None]
        slot = lanes[(w + row0 * W).reshape(-1)].reshape(
            lead + (size, B))
        slot = jnp.where(live[..., None] & (slot < EB),
                         slot + row0[..., None] * EB, rows * EB)
        vid1 = w[..., None] * B + lane + 1
        return (outs[0].at[slot.reshape(-1)].set(vid1.reshape(-1),
                                                 mode="drop"),)

    (first,), trips, looped = _by_need(
        mark_firsts, (jnp.zeros((rows * EB,), jnp.int32),),
        jnp.max(nwords), LW, chunk=CW)
    row = jnp.maximum(jax.lax.cummax(first.reshape(lead + (EB,)),
                                     axis=len(lead)) - 1, 0)
    run = W + (trips if looped else 1) * CW * B
    return (total, total > EB, (None, _row_offsets(indptr, starts), row),
            run, 2 * vmax)


def _expand_plan(over, blk, pid, fbm, EB: int, chunk: int):
    """Lay one block's CSR expansion out from the frontier bitmap(s):
    everything that is a streaming pass over the bitmap or the EB
    slots, and the scatters that turn members into slot→source-row
    tables.  `over`, `blk` and `pid` are `_traverse`'s.

    The plan's cost follows the frontier, not the part: on the chip a
    scatter costs 6 to 7 ns an update, dropped or not, and the
    whole-bitmap plan's two scatters a hop were 56.5 of a 73.3 ms
    program at 1,500,000 local vertices a part whatever the frontier
    held (PERF.md section 6, PR 28).  The choice is the STATIC bitmap
    width: at or under `chunk` the whole-bitmap plan (`_plan_whole`,
    the program PR 28 ran), over it the member plan (`_plan_members`),
    whose trip count is the traced number of words that hold a member.
    A frontier that fills every word runs every trip and issues HALF
    the whole-bitmap plan's updates (one scatter, not two) plus one
    per word, so there is no break-even to fall back at, hence no
    second threshold.  Chip readings of the plan alone (PERF.md
    section 6, PR 29; whole-bitmap → member): one part of 1,500,000
    vertices, EB 2^18, 20.28 ms → 1.05 (empty frontier) to 2.32 (1% of
    the vertices); 8 parts of 125,000, EB 2^22, 16.4 → 2.9 (empty),
    18.4 → 12.0 (every vertex).

    Returns (total, ovf, plan, run, budget) — the true expansion size,
    the overflow flag, the tables `_expand_slots` reads, and the
    scatter updates issued and what the whole-bitmap plan issues, per
    part (0, 0 where that plan was compiled)."""
    if fbm.shape[-1] <= chunk:
        return over(lambda b, _p, f: _plan_whole(b["indptr"], f, EB))(
            blk, pid, fbm) + (0, 0)
    return _plan_members(blk["indptr"], fbm, EB, chunk)


def _expand_slots(nbr, rank, plan, total, lo, size: int, EB: int,
                  P: int, pid, vmax_local: int = 0, hub_dense=None):
    """The per-slot half: slots [lo, lo + size) of the expansion
    `_expand_plan` laid out.  Returns arrays of length `size`:
      src (frontier dense id), dst, rk (the edge's rank; None where
      `rank` is: a program gathers it only for a consumer that reads
      it), eidx (index into the block's edge arrays — the host uses it
      to decode properties), ve (slot valid).

    Every gather here takes one index a slot, and a slot costs what its
    gathers cost (14 to 20 ns an index on the chip, PERF.md section 5):
    `nbr[eidx]`, the plan's row-offset table `off[row]`
    (`_row_offsets`), `vid_of[row]` on the whole-bitmap plan, `rank[eidx]`
    on demand, `hub_dense` with a degree split."""
    vid_of, off, crow = plan
    with jax.named_scope("hop/expand"):
        row = jnp.maximum(_window(crow, lo, size), 0)
        if vid_of is not None:      # the whole-bitmap plan's compact rows
            row = vid_of[row]
        j = lo + jnp.arange(size, dtype=jnp.int32)
        ve = j < jnp.minimum(total, EB)
        eidx = jnp.where(ve, off[row] + j, 0).astype(jnp.int32)
    with jax.named_scope("hop/gather"):
        # the neighbour gather over the slots, and the rank's if asked
        dst = jnp.where(ve, nbr[eidx], -1)
        if hub_dense is None:
            src_id = row * P + pid
        else:
            src_id = jnp.where(
                row < vmax_local, row * P + pid,
                hub_dense[jnp.clip(row - vmax_local, 0,
                                   hub_dense.shape[0] - 1)])
        src = jnp.where(ve, src_id, -1)
        rk = None if rank is None else jnp.where(ve, rank[eidx], 0)
    return src, dst, rk, eidx, ve


def _slot_gathers(plan, rank_on: bool, pred_cols: int, hubs: bool) -> int:
    """Gathers with one index a slot that the expansion stage of a hop
    issues over `plan` (`_expand_slots`, and the predicate's columns
    beside it): what a slot of that hop costs, as a count."""
    return 2 + (plan[0] is not None) + rank_on + pred_cols + hubs


def take_halves(col, i):
    """One part's pinned property column `(2, E)` (device.py
    `split_halves`) at the edge indices `i`: both 32-bit halves by the
    one index array, `(2,) + i.shape`.  The only way a program reads a
    property column: every operand of a gather is 32-bit, so no run
    splits a whole column on a chip without 64-bit lanes."""
    return col[:, i]


def _drop_tombstoned(tomb, eidx, ve):
    """The delta merge's per-slot half: a searchsorted membership test
    drops base slots whose eidx was deleted/overwritten since the pin.
    tomb: (Tcap,) SORTED int32 base-edge indices (MAXI-padded)."""
    if not tomb.shape[0]:
        return ve
    pos = jnp.clip(jnp.searchsorted(tomb, eidx), 0, tomb.shape[0] - 1)
    return ve & ~(tomb[pos] == eidx)


def _delta_rows(dl, fbm, P: int, pid):
    """The delta merge's other half, one part's: the delta rows whose
    source vertex is on the frontier, as (src, dst, rank, active) over
    the Dcap slots (-1, -1, 0 where a slot is not active).  Delta row j
    takes the virtual edge index emax + j, so the host can split
    captured rows back into base (< emax) and delta halves."""
    dsrc = dl["d_src"]
    active = dl["d_valid"] & fbm[jnp.clip(dsrc, 0, fbm.shape[0] - 1)]
    return (jnp.where(active, dsrc * P + pid, -1),
            jnp.where(active, dl["d_dst"], -1),
            jnp.where(active, dl["d_rank"], 0), active)


def _live_rows(over, b, pid, fbm, P: int, has_rows, rank_dtype,
               pred=None, pcols=()):
    """The plane's rows out of a frontier, kept APART from the EB base
    slots and only where the plane holds any (`has_rows`, a traced
    scalar): (src, dst, rank, kept, active), each lead + (Dcap,), all
    off where it holds none.  `pred` sees a delta row's own columns
    (`d_props`, their halves) as its predicate columns.  Delta
    snapshots are never hub-extended, so `fbm` is the plain membership
    row."""
    dcap = _delta_cap(b)
    lead = fbm.shape[:-1]

    def rows(f):
        s, d, r, act = over(
            lambda blk, pd, fb: _delta_rows(blk, fb, P, pd))(b, pid, f)
        k = act
        if pred is not None:
            k = pred({"_rank": r, "_src": s, "_dst": d, **{
                c: jnp.broadcast_to(b["d_props"][c], s.shape[:-1]
                                    + b["d_props"][c].shape[-2:])
                for c in pcols}}) & act
        return s, d, r.astype(rank_dtype), k, act

    def no_rows(f):
        z = jnp.zeros(lead + (dcap,), jnp.int32)
        off = jnp.zeros(lead + (dcap,), bool)
        return z - 1, z - 1, z.astype(rank_dtype), off, off

    with jax.named_scope("hop/delta_merge"):
        return _when(has_rows, rows, no_rows, fbm)


def _drop_live_tombstones(over, b, pid, eidx, ve, has_tomb):
    """`_drop_tombstoned` over every part, only where the plane holds a
    tombstone (`has_tomb`, a traced scalar)."""
    with jax.named_scope("hop/delta_merge"):
        return _when(
            has_tomb,
            over(lambda t, _p, e, v: _drop_tombstoned(t, e, v)),
            lambda _t, _p, e, v: v, b["d_tomb"], pid, eidx, ve)


def _mark_rows(over, marks, dst, keep, P: int, vmax: int, has_rows):
    """The plane's rows marked into a block's mark matrices, only where
    it holds any."""
    return _when(
        has_rows,
        over(lambda _b, _p, m, d, k: _mark(d, k, P, vmax, m)),
        lambda _b, _p, m, d, k: m, None, None, marks, dst, keep)


def _delta_live(b):
    """(tombstones live, rows live) of one block's delta plane, as
    traced scalars over every part (and shard-resident lane) the program
    holds: what each merge stage's `_when` follows, so that an armed
    plane with nothing in it runs none of them.  A part's rows fill its
    buffer from slot 0 and its tombstones sort before their MAXI
    padding (`HostDelta.block_arrays`), so slot 0 tells."""
    return (jnp.any(b["d_tomb"][..., 0] != MAXI),
            jnp.any(b["d_valid"][..., 0]))


def _gather_merged(over, b, pid, c: str, e, emax: int, has_rows):
    """Column `c` of a block at the (virtual) edge indices `e`, as its
    halves (`take_halves`): the base column's values, and where the
    plane holds rows the delta column's at the indices from `emax` on.
    Two gathers, each from its own column: the base column is never
    copied to be extended."""
    take = over(lambda col, _p, i: take_halves(col, i))
    got = take(b["props"][c], pid, jnp.minimum(e, emax - 1))
    dcap = b["d_props"][c].shape[-1]
    return _when(
        has_rows,
        lambda g, i: jnp.where(
            (i >= emax)[..., None, :],
            take(b["d_props"][c], pid, jnp.clip(i - emax, 0, dcap - 1)), g),
        lambda g, i: g, got, e)


def _delta_cap(b) -> int:
    """Extra capture width a block's delta plane adds (0 = no delta)."""
    return int(b["d_src"].shape[-1]) if "d_src" in b else 0


@_stage("hop/mark")
def _mark(dst, keep, P: int, vmax: int, acc=None):
    """Scatter keep-passing dense dst ids into a (P, vmax) ownership
    bitmap: row d = the candidate set destined for part d.  This is the
    sort-free dedup + route: duplicates set the same bit, and the row
    index IS the routing bucket (no argsort, no bucket overflow)."""
    owner = jnp.where(keep, dst % P, 0).astype(jnp.int32)
    loc = jnp.where(keep, dst // P, 0).astype(jnp.int32)
    m = jnp.zeros((P, vmax), bool) if acc is None else acc
    return m.at[owner, loc].max(keep)


@_stage("hop/mark")
def _mark_flat(acc, ids, keep, P: int, vmax: int):
    """`_mark` into a FLAT (P * vmax,) ownership bitmap that a by-need
    loop carries (`_by_need`; algo/frontier.py's level bodies): bit
    `(id % P) * vmax + id // P` is set for every kept dense id of `ids`,
    whatever leading axes `ids` has, so every part's window of a level
    marks into the one bitmap (on one chip the OR over source parts is
    the scatter itself).  Flat because a scatter on the chip works on a
    flat operand: a loop that carried the rows as rows would re-lay
    them out on every trip (`_compact_cap`)."""
    at = jnp.where(keep, (ids % P) * vmax + ids // P, P * vmax)
    return acc.at[at.reshape(-1)].set(True, mode="drop")


def _pack_bits(m):
    """(..., P, vmax) bool → (..., P, W) uint32 words (W = ceil(vmax/32)):
    the mark matrix is bit-packed BEFORE the inter-chip exchange, cutting
    the all_to_all payload 8× vs bool (at SF300 scale: ~35 MB/chip/hop
    instead of ~280 MB).  Packing is a shift-weighted sum over disjoint
    bits (sum of distinct powers of two == OR — no overflow)."""
    vmax = m.shape[-1]
    W = -(-vmax // 32)
    pad = W * 32 - vmax
    mb = jnp.pad(m, ((0, 0),) * (m.ndim - 1) + ((0, pad),))
    bits = mb.reshape(m.shape[:-1] + (W, 32)).astype(jnp.uint32)
    weights = jnp.left_shift(jnp.uint32(1),
                             jnp.arange(32, dtype=jnp.uint32))
    return jnp.sum(bits * weights, axis=-1, dtype=jnp.uint32)


def _unpack_or(recv, vmax: int):
    """(..., P, W) received words → (..., vmax) bool: OR the P rows on
    PACKED words, then unpack once."""
    ored = recv[..., 0, :]
    for i in range(1, recv.shape[-2]):
        ored = ored | recv[..., i, :]
    bits = (ored[..., None] >> jnp.arange(32, dtype=jnp.uint32)) & 1
    return bits.reshape(ored.shape[:-1] + (-1,))[..., :vmax].astype(bool)


@_stage("hop/exchange")
def _exchange_marks(marks, P: int, vmax: int):
    """The per-hop frontier exchange: row d of `marks` is part d's
    candidate bitmap; ship it there (ONE all_to_all over ICI, packed)
    and OR what this part received.  `marks` is (P, vmax), or
    (Ll, P, vmax) with one mark matrix per resident query lane: still
    ONE `all_to_all` per hop, split and concatenated over the part axis
    of a payload that carries the lanes x parts grid, so L compatible
    queries share the ICI transfer.  Returns (vmax,) or (Ll, vmax)."""
    packed = _pack_bits(marks)
    ax = packed.ndim - 2
    recv = jax.lax.all_to_all(packed, "part", ax, ax, tiled=False)
    return _unpack_or(recv.reshape(marks.shape[:-1] + (-1,)), vmax)


def a2a_payload_bytes(P: int, vmax: int, lanes: int = 1) -> int:
    """Total bytes moved through ONE bit-packed frontier all_to_all
    across the whole mesh (sum of every device's send payload): each of
    the P parts ships P rows of ceil(vmax/32) uint32 words per lane.
    Zero when P == 1 — local mode has no exchange."""
    if P <= 1:
        return 0
    W = -(-vmax // 32)
    return int(lanes) * P * P * W * 4


@_stage("hop/compact")
def _compact_cap(cols, keep, n, EB: int, tail: int = 0,
                 chunk: int = CHUNK, tail_live=None):
    """Stable-partition the kept edge slots to the FRONT of each capture
    row (cumsum scatter, O(slots)) and return the kept count.  `cols`
    are the capture's identity columns by name (`_CAP_FILL`'s, of which
    a program holds `rank` only on demand).

    Why: capture arrays are EB-padded and EB is sized for the worst hop
    (millions of slots); fetching them wholesale ships mostly padding
    (~2 GB/query at north-star shape).  With kept entries compacted to a
    prefix the host fetches only [:kmax] slices (fetch.py `Fetcher.fetch`).
    The scatter is order-preserving, so the (part, src)-contiguous
    ascending-eidx invariant the host materializers rely on survives.

    The arrays carry the builder's leading axes before the EB + tail
    slots.  The cumsum is a streaming pass and stays whole; the
    scatters, one a column, run by need (only the first `n` slots and,
    where `tail_live`, the tail can hold a kept entry) into FLAT
    outputs, every row at its own offset:
    a scatter on the chip works on a flat operand, and a loop that
    carried the rows as rows would re-lay all of them out on every
    trip.

    Returns (compacted cols, kcount, chunks run, chunks budgeted).
    """
    W = EB + tail
    rows = int(np.prod(keep.shape[:-1], dtype=np.int64))
    row0 = (jnp.arange(rows, dtype=jnp.int32) * W).reshape(
        keep.shape[:-1] + (1,))
    pos = jnp.where(keep,
                    jnp.cumsum(keep, axis=-1, dtype=jnp.int32) - 1 + row0,
                    rows * W).astype(jnp.int32)
    vals = tuple(jnp.where(keep, v, -1) if k == "dst" else v
                 for k, v in cols.items())

    def scatter(outs, lo, size):
        at = _window(pos, lo, size).reshape(-1)
        return tuple(o.at[at].set(_window(v, lo, size).reshape(-1),
                                  mode="drop")
                     for o, v in zip(outs, vals))

    init = tuple(jnp.full((rows * W,), _CAP_FILL[k], v.dtype)
                 for k, v in cols.items())
    outs, run, budget = _by_need(scatter, init, n, EB, tail, chunk,
                                 tail_live)
    return ({k: o.reshape(keep.shape) for k, o in zip(cols, outs)},
            jnp.sum(keep, axis=-1, dtype=jnp.int32), run, budget)


def _norm_ebs(EB, steps: int, capture_hops: bool):
    """Per-hop edge budgets: an int is uniform; a sequence gives each
    hop its own bucket (a 3-hop GO's first hop expands a few hundred
    edges while the last expands millions — one uniform bucket made
    every hop pay the final hop's padding).  capture_hops mode stacks
    per-hop capture arrays along a hop axis, which requires equal EB."""
    ebs = tuple([EB] * steps) if isinstance(EB, int) else tuple(EB)
    assert len(ebs) == steps, (ebs, steps)
    if capture_hops:
        assert len(set(ebs)) == 1, "capture_hops requires uniform EB"
    return ebs


def _hub_consts(hub_dense, P: int):
    """Static per-snapshot hub tables for the degree-split expansion:
    (dense ids, owner part, owner-local index) as jnp constants, or
    (None, None, None) for an unsplit snapshot."""
    if hub_dense is None or len(hub_dense) == 0:
        return None, None, None
    hd = jnp.asarray(np.asarray(hub_dense), jnp.int32)
    return hd, hd % P, hd // P


def _extend_fbm_sharded(fbm, pid, hub_owner, hub_local):
    """Append hub-active bits to one shard's expansion bitmap: each
    hub's frontier bit lives in its OWNER's shard — OR the per-part
    contributions over the mesh so every part expands its chunk of
    each active hub.  fbm is (vmax,), or (Ll, vmax) under the lanes x
    shards grid: the psum sits outside any vmap (the lane axis is a
    leading data axis of its operand), ONE collective for all resident
    lanes."""
    mine = hub_owner == pid
    vals = jnp.where(mine, fbm[..., hub_local], False)
    bits = jax.lax.psum(vals.astype(jnp.int32), "part") > 0
    return jnp.concatenate([fbm, bits], axis=-1)


def _extend_fbm_local(fbm, hub_owner, hub_local, P: int):
    """Single-chip variant: the full (P, vmax) ownership bitmap is
    resident — gather each hub's bit straight from its owner row and
    replicate across the part axis."""
    bits = fbm[hub_owner, hub_local]                       # (H,)
    return jnp.concatenate(
        [fbm, jnp.broadcast_to(bits, (P, bits.shape[0]))], axis=1)


# The identity columns a capture CAN hold, in their order, each with the
# value its slots keep where the hop put nothing.  `rank` is held by the
# programs built to carry it (`build_traverse_fn`'s `carry_rank`); beside
# them a capture has `kcount` and a `prop:<name>` a yielded column.
_CAP_FILL = {"src": -1, "dst": -1, "rank": 0, "eidx": 0}


def _traverse(over, nlead: int, blocks, fbm, pid, extend, exchange, *,
              P: int, ebs, pred, pred_cols, capture: bool,
              capture_hops: bool, yield_cols, carry_rank: bool, hubs_c,
              chunk: int, plan_chunk: int, noted: dict):
    """The N-hop program, written once for every layout of
    `build_traverse_fn`.

    Every array carries the layout's `nlead` leading axes (none inside
    one shard, the part axis on one chip, the lane axis inside one
    shard of the lanes x shards grid) before its own; `over(f)` maps a
    per-part function f(block_leaves, pid, *arrays) over them, `extend`
    appends the hub bits to a frontier bitmap and `exchange` turns a
    hop's mark matrices into the next frontier.  `blocks` holds each
    block's leaves as `over` expects them.

    Per hop and block: lay the expansion out (`_expand_plan`, from the
    frontier's members where the bitmap is wider than `plan_chunk`),
    then run the per-slot stages by need (`_by_need`) — the expansion's
    gathers with the delta plane's tombstone test and the predicate's
    column gathers in one loop, the capture's compaction scatters in a
    second, the yielded property gathers in a third.  An edge's rank is
    gathered, carried, compacted and captured only with `carry_rank`:
    without it no hop reads a block's `rank` leaf.  `noted` takes what
    the trace settles of the program (`slot_gathers`).

    Returns the result dict of `build_traverse_fn` without its shard
    axis."""
    steps = len(ebs)
    vmax = fbm.shape[-1]
    # a MATCH program captures every hop as a frame; a GO its last hop
    cap_scope = "match/frame_capture" if capture_hops else "hop/capture"
    gcols = [c for c in pred_cols if not c.startswith("_")] \
        if pred is not None else []
    # the identity columns this program's slots carry, in capture order
    names = tuple(k for k in _CAP_FILL if carry_rank or k != "rank")
    hop_edges, frontier_sizes = [], []     # popcount entering each hop
    chunks_run, chunks_budget = [], []
    plan_run, plan_budget = [], []
    ovf_e = None
    hop_caps = []

    for hop, EB in enumerate(ebs):
        frontier_sizes.append(jnp.sum(fbm, axis=-1, dtype=jnp.int32))
        last = hop == steps - 1
        marks = None
        edges = run = budget = prun = pbudget = 0
        caps = {k: [] for k in names + ("kcount",)}
        efbm = fbm if hubs_c is None else extend(fbm)
        want_pred = pred is not None and (last or capture_hops)
        want_cap = capture and (last or capture_hops)
        hcols = gcols if want_pred else []     # columns gathered this hop
        for b in blocks:
            dcap = _delta_cap(b)
            emax = b["nbr"].shape[-1]
            # an armed delta plane costs this block what it HOLDS: every
            # stage it adds sits behind one of these two traced scalars
            has_tomb, has_rows = _delta_live(b) if dcap else (None, None)
            total, ovf, plan, r, bd = _expand_plan(
                over, b, pid, efbm, EB, plan_chunk)
            prun, pbudget = prun + r, pbudget + bd
            if last:
                noted["slot_gathers"] = _slot_gathers(
                    plan, carry_rank, len(hcols), hubs_c is not None)
            # the live slots of the fullest part (or lane): the trip
            # count of every by-need loop of this block
            n = jnp.minimum(jnp.max(total), EB)

            def expand(outs, lo, size):
                def part(blk, pd, pl, tot):
                    s, d, r, e, v = _expand_slots(
                        blk["nbr"], blk["rank"] if carry_rank else None,
                        pl, tot, lo, size, EB, P, pd, vmax, hubs_c)
                    with jax.named_scope("hop/pred_gather"):
                        g = tuple(take_halves(blk["props"][c], e)
                                  for c in hcols)
                    got = {"src": s, "dst": d, "rank": r, "eidx": e}
                    return (v,) + tuple(got[k] for k in names) + g
                vals = over(part)(b, pid, plan, total)
                if dcap:
                    v = _drop_live_tombstones(
                        over, b, pid, vals[len(names)], vals[0], has_tomb)
                    vals = (v,) + vals[1:]
                return tuple(_put(o, v, lo) for o, v in zip(outs, vals))

            lead = total.shape
            rank_dtype = b["rank"].dtype
            outs = (jnp.zeros(lead + (EB,), bool),) + tuple(
                jnp.full(lead + (EB,), _CAP_FILL[k],
                         rank_dtype if k == "rank" else jnp.int32)
                for k in names)
            # a predicate's columns ride the loop as their halves, which
            # the compiled predicate joins (exprjit.py)
            outs += tuple(jnp.zeros(lead + (2, EB), b["props"][c].dtype)
                          for c in hcols)
            outs, r, bd = _by_need(expand, outs, n, EB, chunk=chunk)
            run, budget = run + r, budget + bd
            ve = outs[0]
            ident = dict(zip(names, outs[1:]))
            pcols = dict(zip(hcols, outs[1 + len(names):]))
            dst = ident["dst"]
            if dcap:
                tsrc, tdst, trk, tkeep, tact = _live_rows(
                    over, b, pid, efbm, P, has_rows, rank_dtype,
                    pred if want_pred else None, hcols)
                base_total = total
                total = total + jnp.sum(tact, axis=-1, dtype=jnp.int32)
            ovf_e = ovf if ovf_e is None else ovf_e | ovf
            edges = edges + total

            if want_pred:
                with jax.named_scope("hop/predicate"):
                    # `_src`, `_dst`, and `_rank` where it is carried
                    keep = pred({**{"_" + k: v for k, v in ident.items()
                                    if k != "eidx"}, **pcols}) & ve
            else:
                keep = ve
            if want_cap:
                if dcap:
                    def compact(c, k):
                        got, kc, r, bd = _compact_cap(
                            c, k, n, EB, dcap, chunk, has_rows)
                        return (got, kc, jnp.asarray(r, jnp.int32),
                                jnp.asarray(bd, jnp.int32))

                    tail = {"src": tsrc, "dst": tdst, "rank": trk,
                            "eidx": jnp.broadcast_to(
                                emax + jnp.arange(dcap, dtype=jnp.int32),
                                lead + (dcap,))}
                    wides = {k: jnp.concatenate([ident[k], tail[k]], axis=-1)
                             for k in names}
                    wkeep = jnp.concatenate([keep, tkeep], axis=-1)
                    with jax.named_scope(cap_scope):
                        if want_pred:
                            got = compact(wides, wkeep)
                        else:
                            # nothing tombstoned and no row appended:
                            # the live slots already are the prefix,
                            # as in a program without the plane
                            z = jnp.zeros((), jnp.int32)
                            got = _when(
                                has_tomb | has_rows, compact,
                                lambda c, _k: (
                                    c, jnp.minimum(base_total, EB), z, z),
                                wides, wkeep)
                    kept, kc, r, bd = got
                    run, budget = run + r, budget + bd
                elif want_pred:
                    with jax.named_scope(cap_scope):
                        kept, kc, r, bd = _compact_cap(
                            ident, keep, n, EB, dcap, chunk)
                    run, budget = run + r, budget + bd
                else:
                    # nothing filtered a slot out: the expansion's live
                    # slots already are the prefix, fills and all
                    kept = ident
                    kc = jnp.minimum(total, EB)
                for k in names:
                    caps[k].append(kept[k])
                caps["kcount"].append(kc)
                ce = kept["eidx"]
                if last and not capture_hops and yield_cols:
                    def props(outs, lo, size):
                        e = _window(ce, lo, size)
                        got = []
                        for c, o in zip(yield_cols, outs):
                            with jax.named_scope("hop/prop_" + c):
                                if dcap:
                                    g = _gather_merged(over, b, pid, c, e,
                                                       emax, has_rows)
                                else:
                                    g = over(lambda col, _p, i:
                                             take_halves(col, i))(
                                        b["props"][c], pid, e)
                                got.append(_put(o, g, lo))
                        return tuple(got)

                    # kept entries sit in a prefix: the live range is
                    # the fullest part's kept count, which reaches the
                    # tail only where it passes the budget
                    kmax = jnp.max(kc)
                    # a yielded column stays its halves through the
                    # loop and into the capture: lead + (2, EB + tail)
                    got, r, bd = _by_need(
                        props, tuple(jnp.zeros(
                            ce.shape[:-1] + (2, ce.shape[-1]),
                            b["props"][c].dtype) for c in yield_cols),
                        kmax, EB, dcap, chunk, kmax > EB if dcap else None)
                    run, budget = run + r, budget + bd
                    for c, g in zip(yield_cols, got):
                        caps.setdefault("prop:" + c, []).append(g)
            if not last:
                blk_marks = over(
                    lambda _b, _p, d, k: _mark(d, k, P, vmax))(
                    None, None, dst, keep)
                if dcap:
                    blk_marks = _mark_rows(over, blk_marks, tdst, tkeep,
                                           P, vmax, has_rows)
                marks = blk_marks if marks is None else marks | blk_marks
        hop_edges.append(edges)
        zero = jnp.zeros_like(edges)
        chunks_run.append(zero + run)
        chunks_budget.append(zero + budget)
        plan_run.append(zero + prun)
        plan_budget.append(zero + pbudget)
        if want_cap:
            # arrays lead + (nb, EB); kcount lead + (nb,)
            hop_caps.append({k: jnp.stack(v, axis=nlead)
                             for k, v in caps.items()})
        # the post-final frontier is not needed for GO; report empty
        fbm = jnp.zeros_like(fbm) if last else exchange(marks)

    res = {
        "frontier": fbm,
        "fcount": jnp.sum(fbm, axis=-1, dtype=jnp.int32),
        # pre-filter expansion size per hop: lead + (steps,)
        "hop_edges": jnp.stack(hop_edges, axis=nlead),
        # deterministic work counter (ISSUE 1): per-hop frontier size,
        # this part's members only — host sums over parts
        "frontier_sizes": jnp.stack(frontier_sizes, axis=nlead),
        "ovf_expand": ovf_e,
        # by-need engagement: loop trips run and budgeted per hop, one
        # chunk being CHUNK slots of one part (0 where no loop ran)
        "chunks_run": jnp.stack(chunks_run, axis=nlead),
        "chunks_budget": jnp.stack(chunks_budget, axis=nlead),
        # member-plan engagement: scatter updates the hops' plans issued
        # and what whole-bitmap plans issue (0 where those were compiled)
        "plan_run": jnp.stack(plan_run, axis=nlead),
        "plan_budget": jnp.stack(plan_budget, axis=nlead),
    }
    if capture:
        if capture_hops:
            with jax.named_scope("match/frame_stack"):
                # lead + (steps, nb, EB); kcount lead + (steps, nb)
                cap = {k: jnp.stack([hc[k] for hc in hop_caps], axis=nlead)
                       for k in hop_caps[0]}
        else:
            cap = dict(hop_caps[-1])
        res["kcount"] = cap.pop("kcount")   # small: fetched with the meta
        res["cap"] = cap
    return res


def _part_view(blocks_data):
    """One shard's blocks without their shard axis (length 1 inside a
    shard_map over 'part')."""
    return [jax.tree.map(lambda x: x[0], b) for b in blocks_data]


def build_traverse_fn(mesh, P: int, EB, steps: int, n_blocks: int, *,
                      lanes: bool = False,
                      pred: Optional[Callable[[Dict[str, Any]], Any]] = None,
                      pred_cols: Sequence[str] = (),
                      capture: bool = True,
                      capture_hops: bool = False,
                      yield_cols: Sequence[str] = (),
                      carry_rank: bool = True,
                      hub_dense=None, chunk: int = CHUNK,
                      plan_chunk: int = PLAN_CHUNK):
    """Compile the N-step traversal program for one bucket configuration.
    EB: per-block edge budget — an int (uniform) or a per-hop sequence.

    mesh: the ('part',) or ('lane', 'part') mesh whose part axis holds
    one partition a device — ONE `shard_map` program, the frontier
    exchanged by a bit-packed `all_to_all` between hops — or None for
    one chip: all P partitions resident on the device, the per-part
    kernel vmapped over the part axis and the exchange an OR-reduce
    over the mark matrices (marks[s, d] = part s's candidate bitmap for
    part d; the degenerate all_to_all, no ICI).  The two have identical
    semantics.

    lanes: the query-lane-batched program (ISSUE 15): the frontier and
    every result leaf gain a LEADING lane axis, L compatible statements
    (same kernel family, shape bucket, predicate/yield program) share
    one device put, dispatch and fetch, and the runtime de-muxes lane l
    back to its statement by slicing `[l]`.  Lanes are independent
    computations (no cross-lane reduction anywhere), so each lane's
    captured edge set is bit-identical to the same statement's solo
    dispatch at the same edge budget; padding lanes (all-false
    frontier) expand zero edges.  On one chip the CSR blocks are closed
    over once and broadcast across lanes (`in_axes=(None, 0)`).  On a
    mesh they stay mesh-resident (device (l, p) reads partition p's
    adjacency out of its own HBM), the frontier is sharded over BOTH
    axes and a hop's exchange is still ONE `all_to_all` carrying the
    lanes x parts grid; a legacy 1-D ('part',) mesh has no lane axis,
    so every device holds all lanes.

    yield_cols: edge-prop names the caller's YIELD list reads — their
    values are gathered ON DEVICE from the pinned prop columns at the
    compacted final-hop slots and captured as `prop:<name>` arrays, so
    the host fetches exactly the result columns instead of eidx + a
    host-side gather (GO capture mode only).  A column is gathered and
    captured as its 32-bit halves (`take_halves`), which the host joins:
    a carried value comes back to the bit.

    carry_rank: whether the program gathers each expanded edge's rank
    and carries it into the capture.  The caller says False for a
    statement of which nothing reads it (runtime.py `_run_traverse` has
    the rule): the expansion loop then runs one gather a slot fewer, no
    `rank` buffer of the hop's budget exists and `cap` has no `rank`
    entry.  A predicate over `_rank` carries it whatever the caller
    said.

    chunk: the by-need loops' chunk (`_by_need`); plan_chunk: the
    member plan's trip and the bitmap width over which it is compiled
    (`_expand_plan`); the module constants everywhere but in tests.

    blocks_data (runtime arg): tuple of n_blocks dicts with keys
      indptr (P, vmax+1), nbr (P, E), rank (P, E),
      props {name: (P, 2, E) 32-bit halves}
    where props holds the columns the predicate needs PLUS yield_cols
    (any other result prop decodes on host via the captured eidx); the
    delta plane's d_* leaves carry the part axis too.

    Returns jitted fn(blocks_data, frontier) -> dict with (lead = (P,),
    or (L, P) with lanes; `fn.noted` holds, once fn was traced at its
    first run, `slot_gathers`: the gathers with one index a slot in the
    last hop's expansion stage, `_slot_gathers`):
      frontier lead + (vmax,) bool, fcount lead: next frontier after
        the LAST hop (mid-hop frontiers never leave the device)
      hop_edges lead + (steps,): pre-filter expansion size per hop
      chunks_run, chunks_budget lead + (steps,): by-need loop trips run
        and budgeted per hop (0 where the hop's budget fits one chunk)
      plan_run, plan_budget lead + (steps,): scatter updates the hop's
        expansion plans issued, and what whole-bitmap plans issue (0
        where the bitmap is no wider than plan_chunk)
      ovf_expand lead, bool: some hop's expansion exceeded EB
      cap (if capture): dict of lead + (n_blocks, EB) arrays
        src, dst, rank (with carry_rank), eidx, and lead +
        (n_blocks, 2, EB) halves
        prop:<name> per yield_col — the final
        hop's edge set (kept entries compacted to a prefix;
        kcount lead + (n_blocks,) gives the counts; what a prop array
        holds past its kept count is unspecified)

    capture_hops=True is the MATCH mode (SURVEY §2 row 23 Traverse):
    the predicate is applied at EVERY hop (a MATCH edge pattern's filter
    is uniform over a variable-length expansion, unlike GO's final-step
    WHERE) and the edge frame of every hop is captured — cap arrays gain
    a hop axis, lead + (steps, n_blocks, EB).  The host assembles
    trail-semantics paths from the layered frames (assemble.py).
    """
    ebs = _norm_ebs(EB, steps, capture_hops)
    hubs_c, hub_owner, hub_local = _hub_consts(hub_dense, P)
    # what `_traverse` settles of the program while it is traced, at its
    # first run: `slot_gathers`, the per-slot gathers of the last hop's
    # expansion stage (`_slot_gathers`)
    noted: Dict[str, int] = {}
    kw = dict(P=P, ebs=ebs, pred=pred, pred_cols=pred_cols,
              capture=capture, capture_hops=capture_hops,
              yield_cols=yield_cols,
              carry_rank=carry_rank or (pred is not None
                                        and "_rank" in pred_cols),
              hubs_c=hubs_c, chunk=chunk, plan_chunk=plan_chunk,
              noted=noted)

    def program(f):
        fn = jax.jit(f)
        fn.noted = noted
        return fn

    if mesh is None:
        pids = jnp.arange(P, dtype=jnp.int32)

        def fn(blocks_data, frontier):
            return _traverse(
                jax.vmap, 1, blocks_data, frontier, pids,
                lambda f: _extend_fbm_local(f, hub_owner, hub_local, P),
                lambda marks: marks.any(axis=0), **kw)

        return program(jax.vmap(fn, in_axes=(None, 0)) if lanes else fn)

    from jax.sharding import PartitionSpec
    csr_spec = PartitionSpec("part")
    if lanes:
        # the CSR block and the part id are this shard's; everything
        # else carries the lane axis (delta-row activity, too, depends
        # on THIS lane's frontier bitmap)
        def over(f):
            return lambda blk, pd, *xs: jax.vmap(
                lambda *ys: f(blk, pd, *ys))(*xs)
        lane_ax = "lane" if "lane" in mesh.axis_names else None
        fr_spec = PartitionSpec(lane_ax, "part")
        # local (Ll, 1, ...): the shard axis follows the lane axis
        nlead, shard = 1, (slice(None), 0)
    else:
        def over(f):
            return f
        fr_spec = csr_spec
        nlead, shard = 0, (0,)

    def kernel(blocks_data, frontier):
        pid = jax.lax.axis_index("part").astype(jnp.int32)
        vmax = frontier.shape[-1]
        res = _traverse(
            over, nlead, _part_view(blocks_data), frontier[shard], pid,
            lambda f: _extend_fbm_sharded(f, pid, hub_owner, hub_local),
            lambda marks: _exchange_marks(marks, P, vmax), **kw)
        return jax.tree.map(lambda x: jnp.expand_dims(x, nlead), res)

    smapped = _shard_map(kernel, mesh=mesh,
                         in_specs=(csr_spec, fr_spec), out_specs=fr_spec)
    return program(smapped)
