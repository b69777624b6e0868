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

Frontier representation between hops: (P, vmax) bool, row p = the
membership bitmap of part p's local ids (dense id = local * P + p).
Expansion enumerates set bits in ascending local-id order, so captured
edge slots stay (part, src)-contiguous ascending-eidx — the invariant
the host materializers rely on.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Optional, Sequence

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


def _expand_block(indptr, nbr, rank, fbm, EB: int, P: int, pid,
                  vmax_local: int = 0, hub_dense=None):
    """Vectorized CSR expansion of one block from one part's frontier
    bitmap.

    indptr: (vmax+1,) local CSR row pointers; nbr/rank: (E,) edge
    arrays; fbm: (vmax,) bool frontier membership; pid: this part's id
    (dense id = local * P + pid).

    With a degree-split snapshot (graphstore.csr.degree_split) the
    block carries H extra HUB rows after the vmax_local local rows, and
    fbm arrives EXTENDED to vmax_local+H (hub-active bits appended by
    the caller); a hub row's source dense id comes from `hub_dense`
    instead of the local-row arithmetic.

    Slot→source-row assignment is a cumsum-scatter, not a binary
    search: bump +1 at each frontier vertex's first slot, prefix-sum
    over the EB slots, then map the compact row number back to a local
    id through a scattered lookup table — O(vmax + EB) total, versus
    O(EB log vmax) for searchsorted (the log factor dominated the old
    kernel's per-slot cost on both the VPU and the CPU-emulated mesh).

    Returns per-edge-slot arrays of length EB:
      src (frontier dense id), dst, rk, eidx (index into the block's
      edge arrays — the host uses it to decode properties), ve (slot
      valid), plus (total, ovf): true expansion size and overflow flag.
    """
    vmax = fbm.shape[0]
    with jax.named_scope("hop/expand"):
        deg = jnp.where(fbm, indptr[1:] - indptr[:-1], 0).astype(jnp.int32)
        ends = jnp.cumsum(deg)
        total = ends[-1]
        starts = ends - deg                       # (vmax,)
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
        row = vid_of[jnp.maximum(crow, 0)]
        j = jnp.arange(EB, dtype=jnp.int32)
        eidx = indptr[row] + (j - starts[row])
        ve = j < jnp.minimum(total, EB)
        eidx = jnp.where(ve, eidx, 0).astype(jnp.int32)
    with jax.named_scope("hop/gather"):
        # the neighbour and rank gathers over the hop's EB slots
        dst = jnp.where(ve, nbr[eidx], -1)
        if hub_dense is None:
            src_id = row * P + pid
        else:
            src_id = jnp.where(
                row < vmax_local, row * P + pid,
                hub_dense[jnp.clip(row - vmax_local, 0,
                                   hub_dense.shape[0] - 1)])
        src = jnp.where(ve, src_id, -1)
        rk = jnp.where(ve, rank[eidx], 0)
    return src, dst, rk, eidx, ve, total, total > EB


@_stage("hop/delta_merge")
def _merge_delta(dl, fbm, src, dst, rk, eidx, ve, total, P: int, pid,
                 emax: int):
    """Merge the device-resident delta plane into one block's expansion
    (ISSUE 19).

    dl: dict with the block's delta leaves for THIS part —
      d_src (Dcap,) int32 LOCAL source index, d_dst (Dcap,) dense dst,
      d_rank (Dcap,), d_valid (Dcap,) bool slot-live,
      d_tomb (Tcap,) SORTED int32 base-edge indices masked out
      (MAXI-padded).
    fbm: (vmax,) bool — this part's frontier bitmap (delta snapshots are
    never degree-split, so no hub extension applies).

    Two halves, in order:
      1. tombstones: a searchsorted membership test drops base slots
         whose eidx was deleted/overwritten since the pin;
      2. inserts: delta rows whose source vertex is on the frontier are
         APPENDED to the capture arrays — delta row j takes the virtual
         edge index emax + j, so downstream prop gathers read from
         columns extended with the delta prop columns and the host can
         split captured rows back into base (< emax) and delta halves.

    The appended slots keep the ascending-eidx tail position, so the
    (part, src)-contiguous prefix invariant of the BASE slots survives;
    the host re-sorts the merged union per part into canonical CSR
    order (runtime._block_columns) before materializing rows.
    """
    tomb = dl["d_tomb"]
    if tomb.shape[0]:
        pos = jnp.clip(jnp.searchsorted(tomb, eidx), 0, tomb.shape[0] - 1)
        ve = ve & ~(tomb[pos] == eidx)
    dsrc = dl["d_src"]
    Dcap = dsrc.shape[0]
    if Dcap:
        active = dl["d_valid"] & fbm[jnp.clip(dsrc, 0, fbm.shape[0] - 1)]
        src = jnp.concatenate([src, jnp.where(active, dsrc * P + pid, -1)])
        dst = jnp.concatenate([dst, jnp.where(active, dl["d_dst"], -1)])
        rk = jnp.concatenate([rk, jnp.where(active, dl["d_rank"], 0)])
        eidx = jnp.concatenate(
            [eidx, emax + jnp.arange(Dcap, dtype=jnp.int32)])
        ve = jnp.concatenate([ve, active])
        total = total + jnp.sum(active, dtype=jnp.int32)
    return src, dst, rk, eidx, ve, total


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


def _pack_bits(m):
    """(P, vmax) bool → (P, W) uint32 words (W = ceil(vmax/32)): the
    mark matrix is bit-packed BEFORE the inter-chip exchange, cutting
    the all_to_all payload 8× vs bool (at SF300 scale: ~35 MB/chip/hop
    instead of ~280 MB).  Packing is a shift-weighted sum over disjoint
    bits (sum of distinct powers of two == OR — no overflow)."""
    P, vmax = m.shape
    W = -(-vmax // 32)
    pad = W * 32 - vmax
    mb = jnp.pad(m, ((0, 0), (0, pad)))
    bits = mb.reshape(P, W, 32).astype(jnp.uint32)
    weights = jnp.left_shift(jnp.uint32(1),
                             jnp.arange(32, dtype=jnp.uint32))
    return jnp.sum(bits * weights, axis=-1, dtype=jnp.uint32)


def _unpack_or(recv, vmax: int):
    """(P, W) received words → (vmax,) bool: OR the P rows on PACKED
    words, then unpack once."""
    ored = recv[0]
    for i in range(1, recv.shape[0]):
        ored = ored | recv[i]
    bits = (ored[:, None] >> jnp.arange(32, dtype=jnp.uint32)) & 1
    return bits.reshape(-1)[:vmax].astype(bool)


@_stage("hop/exchange")
def _exchange_marks(marks, P: int, vmax: int):
    """The per-hop frontier exchange: row d of `marks` is part d's
    candidate bitmap; ship it there (ONE all_to_all over ICI, packed)
    and OR what this part received."""
    packed = _pack_bits(marks)
    recv = jax.lax.all_to_all(packed, "part", 0, 0, tiled=False)
    return _unpack_or(recv.reshape(P, -1), vmax)


@_stage("hop/exchange")
def _exchange_marks_lanes(marks, P: int, vmax: int):
    """Lane-batched frontier exchange: `marks` is (Ll, P, vmax) — one
    mark matrix per resident query lane.  Still ONE `all_to_all` per hop:
    the packed payload carries the lanes × parts grid in a single
    (Ll, P, W) tensor split/concatenated over the part axis (axis 1), so
    L compatible queries share the ICI transfer instead of paying one
    collective each.  Returns (Ll, vmax) bool — this part's next
    frontier per lane."""
    packed = jax.vmap(_pack_bits)(marks)              # (Ll, P, W)
    recv = jax.lax.all_to_all(packed, "part", 1, 1, tiled=False)
    return jax.vmap(lambda r: _unpack_or(r, vmax))(recv)


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
def _compact_cap(src, dst, rk, eidx, keep, EB: int):
    """Stable-partition the kept edge slots to the FRONT of each capture
    row (cumsum scatter, O(EB)) and return the kept count.

    Why: capture arrays are EB-padded and EB is sized for the worst hop
    (millions of slots); fetching them wholesale ships mostly padding
    (~2 GB/query at north-star shape).  With kept entries compacted to a
    prefix the host fetches only [:kmax] slices (runtime._escalate).
    The scatter is order-preserving, so the (part, src)-contiguous
    ascending-eidx invariant the host materializers rely on survives."""
    pos = jnp.where(keep, jnp.cumsum(keep, dtype=jnp.int32) - 1,
                    EB).astype(jnp.int32)

    def put(a, fill):
        return jnp.full((EB,), fill, a.dtype).at[pos].set(a, mode="drop")

    return (put(src, -1), put(jnp.where(keep, dst, -1), -1), put(rk, 0),
            put(eidx, 0), jnp.sum(keep, dtype=jnp.int32))


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
    each active hub."""
    mine = hub_owner == pid
    vals = jnp.where(mine, fbm[hub_local], False)
    bits = jax.lax.psum(vals.astype(jnp.int32), "part") > 0
    return jnp.concatenate([fbm, bits])


def _extend_fbm_sharded_lanes(fbm, pid, hub_owner, hub_local):
    """Lane-batched hub extension: fbm is (Ll, vmax) — gather each
    lane's owned hub bits and psum over the part axis in ONE collective
    for all resident lanes (the collective sits OUTSIDE any vmap: the
    lane axis is just a leading data axis of the psum operand)."""
    mine = hub_owner == pid                               # (H,)
    vals = jnp.where(mine[None, :], fbm[:, hub_local], False)
    bits = jax.lax.psum(vals.astype(jnp.int32), "part") > 0
    return jnp.concatenate([fbm, bits], axis=1)           # (Ll, vmax+H)


def _extend_fbm_local(fbm, hub_owner, hub_local, P: int):
    """Single-chip variant: the full (P, vmax) ownership bitmap is
    resident — gather each hub's bit straight from its owner row and
    replicate across the part axis."""
    bits = fbm[hub_owner, hub_local]                       # (H,)
    return jnp.concatenate(
        [fbm, jnp.broadcast_to(bits, (P, bits.shape[0]))], axis=1)


def build_traverse_fn(mesh, P: int, EB, steps: int,
                      n_blocks: int,
                      pred: Optional[Callable[[Dict[str, Any]], Any]] = None,
                      pred_cols: Sequence[str] = (),
                      capture: bool = True,
                      capture_hops: bool = False,
                      yield_cols: Sequence[str] = (),
                      hub_dense=None):
    """Compile the N-step traversal program for one bucket configuration.
    EB: per-block edge budget — an int (uniform) or a per-hop sequence.

    yield_cols: edge-prop names the caller's YIELD list reads — their
    values are gathered ON DEVICE from the pinned prop columns at the
    compacted final-hop slots and captured as `prop:<name>` arrays, so
    the host fetches exactly the result columns instead of eidx + a
    host-side gather (GO capture mode only; x64 is enabled, so device
    gathers are bit-exact with the host decode).

    blocks_data (runtime arg): tuple of n_blocks dicts with keys
      indptr (P, vmax+1), nbr (P, E), rank (P, E), props {name: (P, E)}
    where props holds the columns the predicate needs PLUS yield_cols
    (any other result prop decodes on host via the captured eidx).

    Returns jitted fn(blocks_data, frontier) -> dict with:
      frontier (P, vmax) bool, fcount (P,): next frontier after the LAST
        hop (mid-hop frontiers never leave the device)
      hop_edges (P, steps): pre-filter expansion size per hop per part
      ovf_expand (P,) bool: some hop's expansion exceeded EB
      cap (if capture): dict of (P, n_blocks, EB) arrays
        src, dst, rank, eidx, prop:<name> per yield_col — the final
        hop's edge set (kept entries compacted to a prefix;
        kcount (P, n_blocks) gives the counts)

    capture_hops=True is the MATCH mode (SURVEY §2 row 23 Traverse):
    the predicate is applied at EVERY hop (a MATCH edge pattern's filter
    is uniform over a variable-length expansion, unlike GO's final-step
    WHERE) and the edge frame of every hop is captured — cap arrays gain
    a leading hop axis, (P, steps, n_blocks, EB).  The host assembles
    trail-semantics paths from the layered frames (runtime.py).
    """

    ebs = _norm_ebs(EB, steps, capture_hops)
    # a MATCH program captures every hop as a frame; a GO its last hop
    cap_scope = "match/frame_capture" if capture_hops else "hop/capture"
    hubs_c, hub_owner, hub_local = _hub_consts(hub_dense, P)

    def kernel(blocks_data, frontier):
        fbm = frontier[0]                      # (vmax,) bool
        vmax = fbm.shape[0]
        pid = jax.lax.axis_index("part").astype(jnp.int32)
        hop_edges: List[Any] = []
        frontier_sizes: List[Any] = []         # popcount entering each hop
        ovf_e = jnp.zeros((), bool)
        cap_out = None
        hop_caps: List[Dict[str, Any]] = []

        for hop in range(steps):
            frontier_sizes.append(jnp.sum(fbm, dtype=jnp.int32))
            last = hop == steps - 1
            EBh = ebs[hop]
            marks = None
            edges_this_hop = jnp.zeros((), jnp.int32)
            caps = {"src": [], "dst": [], "rank": [], "eidx": [],
                    "kcount": []}
            efbm = fbm if hubs_c is None else _extend_fbm_sharded(
                fbm, pid, hub_owner, hub_local)
            for bi in range(n_blocks):
                b = blocks_data[bi]
                src, dst, rk, eidx, ve, total, ovf = _expand_block(
                    b["indptr"][0], b["nbr"][0], b["rank"][0], efbm, EBh,
                    P, pid, vmax_local=vmax, hub_dense=hubs_c)
                ovf_e = ovf_e | ovf
                dcap = _delta_cap(b)
                if dcap:
                    dl = {k: b[k][0] for k in
                          ("d_src", "d_dst", "d_rank", "d_valid", "d_tomb")}
                    src, dst, rk, eidx, ve, total = _merge_delta(
                        dl, fbm, src, dst, rk, eidx, ve, total, P, pid,
                        b["nbr"].shape[-1])
                edges_this_hop = edges_this_hop + total

                def _col(name):
                    c = b["props"][name][0]
                    if dcap:
                        c = jnp.concatenate([c, b["d_props"][name][0]])
                    return c

                if pred is not None and (last or capture_hops):
                    cols = {"_rank": rk, "_src": src, "_dst": dst}
                    for name in pred_cols:
                        if not name.startswith("_"):
                            with jax.named_scope("hop/pred_gather"):
                                cols[name] = _col(name)[eidx]
                    with jax.named_scope("hop/predicate"):
                        keep = pred(cols) & ve
                else:
                    keep = ve
                if capture and (last or capture_hops):
                    with jax.named_scope(cap_scope):
                        cs, cd, cr, ce, kc = _compact_cap(
                            src, dst, rk, eidx, keep, EBh + dcap)
                    caps["src"].append(cs)
                    caps["dst"].append(cd)
                    caps["rank"].append(cr)
                    caps["eidx"].append(ce)
                    caps["kcount"].append(kc)
                    if last and not capture_hops:
                        for name in yield_cols:
                            with jax.named_scope("hop/prop_" + name):
                                caps.setdefault("prop:" + name, []).append(
                                    _col(name)[ce])
                if not last:
                    marks = _mark(dst, keep, P, vmax, marks)
            hop_edges.append(edges_this_hop)
            if capture and (last or capture_hops):
                hop_caps.append({k: jnp.stack(v) for k, v in caps.items()})

            if last:
                if capture:
                    if capture_hops:
                        with jax.named_scope("match/frame_stack"):
                            arr_keys = ("src", "dst", "rank", "eidx")
                            cap_out = {
                                k: jnp.stack([hc[k] for hc in hop_caps])[None]
                                for k in arr_keys}
                            kcount_out = jnp.stack(
                                [hc["kcount"] for hc in hop_caps])[None]
                    else:
                        cap_out = {k: v[None]
                                   for k, v in hop_caps[-1].items()
                                   if k != "kcount"}
                        kcount_out = hop_caps[-1]["kcount"][None]
                # the post-final frontier is not needed for GO; report empty
                fbm = jnp.zeros((vmax,), bool)
            else:
                fbm = _exchange_marks(marks, P, vmax)

        res = {
            "frontier": fbm[None],
            "fcount": jnp.sum(fbm, dtype=jnp.int32)[None],
            "hop_edges": jnp.stack(hop_edges)[None],
            # deterministic work counter (ISSUE 1): per-hop frontier
            # size, this shard's members only — host sums over parts
            "frontier_sizes": jnp.stack(frontier_sizes)[None],
            "ovf_expand": ovf_e[None],
        }
        if capture:
            res["cap"] = cap_out
            res["kcount"] = kcount_out   # small: fetched with the meta
        return res

    from jax.sharding import PartitionSpec
    spec = PartitionSpec("part")
    smapped = _shard_map(kernel, mesh=mesh,
                         in_specs=(spec, spec), out_specs=spec)
    return jax.jit(smapped)


def _build_local_fn(P: int, EB, steps: int,
                    n_blocks: int,
                    pred: Optional[Callable[[Dict[str, Any]], Any]] = None,
                    pred_cols: Sequence[str] = (),
                    capture: bool = True,
                    capture_hops: bool = False,
                    yield_cols: Sequence[str] = (),
                    hub_dense=None):
    """The UNJITTED single-chip traversal program — shared by
    build_traverse_fn_local (jit) and build_traverse_fn_lanes (jit of a
    vmap over a leading query-lane axis; ISSUE 15)."""
    pids = jnp.arange(P, dtype=jnp.int32)
    ebs = _norm_ebs(EB, steps, capture_hops)
    # a MATCH program captures every hop as a frame; a GO its last hop
    cap_scope = "match/frame_capture" if capture_hops else "hop/capture"
    hubs_c, hub_owner, hub_local = _hub_consts(hub_dense, P)

    def one_part_expand(block, fbm, pid, want_pred, EBh, vmax_local):
        src, dst, rk, eidx, ve, total, ovf = _expand_block(
            block["indptr"], block["nbr"], block["rank"], fbm, EBh, P,
            pid, vmax_local=vmax_local, hub_dense=hubs_c)
        if "d_src" in block:
            # delta snapshots are never hub-extended, so fbm here is the
            # plain (vmax,) membership row
            src, dst, rk, eidx, ve, total = _merge_delta(
                block, fbm, src, dst, rk, eidx, ve, total, P, pid,
                block["nbr"].shape[-1])
        if want_pred:
            cols = {"_rank": rk, "_src": src, "_dst": dst}
            for name in pred_cols:
                if not name.startswith("_"):
                    c = block["props"][name]
                    if "d_src" in block:
                        c = jnp.concatenate([c, block["d_props"][name]])
                    with jax.named_scope("hop/pred_gather"):
                        cols[name] = c[eidx]
            with jax.named_scope("hop/predicate"):
                keep = pred(cols) & ve
        else:
            keep = ve
        return src, dst, rk, eidx, ve, keep, total, ovf

    def fn(blocks_data, frontier):
        fbm = frontier                     # (P, vmax) bool
        vmax = fbm.shape[1]
        hop_edges = []
        frontier_sizes = []                # popcount entering each hop
        ovf_e = jnp.zeros((P,), bool)
        cap_out = None
        hop_caps = []

        for hop in range(steps):
            frontier_sizes.append(jnp.sum(fbm, axis=1, dtype=jnp.int32))
            last = hop == steps - 1
            EBh = ebs[hop]
            marks = None                   # (P_src, P_dst, vmax) bool
            edges = jnp.zeros((P,), jnp.int32)
            caps = {"src": [], "dst": [], "rank": [], "eidx": [],
                    "kcount": []}
            efbm = fbm if hubs_c is None else _extend_fbm_local(
                fbm, hub_owner, hub_local, P)
            for bi in range(n_blocks):
                b = blocks_data[bi]
                want_pred = pred is not None and (last or capture_hops)
                dcap = _delta_cap(b)
                # the whole block dict is the vmap operand: every leaf
                # (indptr/nbr/rank/props AND the d_* delta plane) carries
                # a leading part axis
                src, dst, rk, eidx, ve, keep, total, ovf = jax.vmap(
                    lambda blk, f, pd: one_part_expand(
                        blk, f, pd, want_pred, EBh, vmax)
                )(b, efbm, pids)
                ovf_e = ovf_e | ovf
                edges = edges + total
                if capture and (last or capture_hops):
                    with jax.named_scope(cap_scope):
                        cs, cd, cr, ce, kc = jax.vmap(
                            lambda s, d, r, e, k: _compact_cap(
                                s, d, r, e, k, EBh + dcap)
                        )(src, dst, rk, eidx, keep)
                    caps["src"].append(cs)
                    caps["dst"].append(cd)
                    caps["rank"].append(cr)
                    caps["eidx"].append(ce)
                    caps["kcount"].append(kc)
                    if last and not capture_hops:
                        for name in yield_cols:
                            col = b["props"][name]
                            if dcap:
                                col = jnp.concatenate(
                                    [col, b["d_props"][name]], axis=1)
                            with jax.named_scope("hop/prop_" + name):
                                caps.setdefault("prop:" + name, []).append(
                                    jax.vmap(lambda c, e: c[e])(col, ce))
                if not last:
                    blk_marks = jax.vmap(
                        lambda d, k: _mark(d, k, P, vmax))(dst, keep)
                    marks = blk_marks if marks is None \
                        else marks | blk_marks
            hop_edges.append(edges)
            if capture and (last or capture_hops):
                # arrays (P, nb, EB); kcount (P, nb)
                hop_caps.append({k: jnp.stack(v, axis=1)
                                 for k, v in caps.items()})

            if last:
                if capture:
                    if capture_hops:
                        with jax.named_scope("match/frame_stack"):
                            arr_keys = ("src", "dst", "rank", "eidx")
                            # (P, steps, nb, EB); kcount (P, steps, nb)
                            cap_out = {k: jnp.stack([hc[k] for hc in hop_caps],
                                                    axis=1)
                                       for k in arr_keys}
                            kcount_out = jnp.stack(
                                [hc["kcount"] for hc in hop_caps], axis=1)
                    else:
                        cap_out = {k: v for k, v in hop_caps[-1].items()
                                   if k != "kcount"}
                        kcount_out = hop_caps[-1]["kcount"]
                fbm = jnp.zeros((P, vmax), bool)
            else:
                # marks[s, d] = part s's candidate bitmap for part d;
                # OR over sources = the exchange + merge in one reduce
                fbm = marks.any(axis=0)

        res = {
            "frontier": fbm,
            "fcount": jnp.sum(fbm, axis=1, dtype=jnp.int32),
            "hop_edges": jnp.stack(hop_edges, axis=1),      # (P, steps)
            "frontier_sizes": jnp.stack(frontier_sizes, axis=1),
            "ovf_expand": ovf_e,
        }
        if capture:
            res["cap"] = cap_out
            res["kcount"] = kcount_out   # small: fetched with the meta
        return res

    return fn


def build_traverse_fn_local(P: int, EB, steps: int,
                            n_blocks: int,
                            pred: Optional[Callable[[Dict[str, Any]], Any]] = None,
                            pred_cols: Sequence[str] = (),
                            capture: bool = True,
                            capture_hops: bool = False,
                            yield_cols: Sequence[str] = (),
                            hub_dense=None):
    """Single-chip variant: all P partitions resident on one device, the
    per-part kernel vmapped over the part axis, and the frontier exchange
    an OR-reduce over the mark matrices (the degenerate all_to_all).
    This is the program that runs on one real chip (the bench config) —
    identical semantics to the sharded build, no ICI.  capture_hops
    follows the sharded contract (MATCH mode: per-hop pred + per-hop
    frames, cap arrays (P, steps, n_blocks, EB)).
    """
    return jax.jit(_build_local_fn(
        P, EB, steps, n_blocks, pred=pred, pred_cols=pred_cols,
        capture=capture, capture_hops=capture_hops,
        yield_cols=yield_cols, hub_dense=hub_dense))


def build_traverse_fn_lanes(P: int, EB, steps: int,
                            n_blocks: int,
                            pred: Optional[Callable[[Dict[str, Any]], Any]] = None,
                            pred_cols: Sequence[str] = (),
                            capture: bool = True,
                            capture_hops: bool = False,
                            yield_cols: Sequence[str] = (),
                            hub_dense=None):
    """Query-lane-batched single-chip program (ISSUE 15 tentpole).

    The same traversal program with a leading QUERY-ID LANE axis vmapped
    over the frontier: L compatible statements (same kernel family, same
    shape bucket, same predicate/yield program) share ONE device put,
    ONE dispatch and ONE fetch — the CSR blocks are closed over once and
    broadcast across lanes (`in_axes=(None, 0)`), so the marginal cost
    of a lane is its own expansion work, not a full kernel launch.

    Inputs/outputs match the local builder's contract with a leading L
    axis added: frontier (L, P, vmax) bool; every result leaf —
    hop_edges, frontier_sizes, ovf_expand, kcount and the cap arrays —
    gains the lane axis, and the runtime de-muxes lane l back to its
    statement by slicing `[l]`.  Lanes are INDEPENDENT computations
    (no cross-lane reduction anywhere), so each lane's captured edge
    set is bit-identical to the same statement's solo dispatch at the
    same edge budget; padding lanes (all-false frontier) expand zero
    edges and only cost their share of the dense kernel shape.
    """
    fn = _build_local_fn(
        P, EB, steps, n_blocks, pred=pred, pred_cols=pred_cols,
        capture=capture, capture_hops=capture_hops,
        yield_cols=yield_cols, hub_dense=hub_dense)
    return jax.jit(jax.vmap(fn, in_axes=(None, 0)))


def build_traverse_fn_lanes_sharded(mesh, P: int, EB, steps: int,
                                    n_blocks: int,
                                    pred: Optional[Callable[[Dict[str, Any]], Any]] = None,
                                    pred_cols: Sequence[str] = (),
                                    capture: bool = True,
                                    capture_hops: bool = False,
                                    yield_cols: Sequence[str] = (),
                                    hub_dense=None):
    """The lanes × shards launch grid: ONE shard_map program over the
    2-axis ("lane", "part") mesh that fuses PR 12's query-id lane axis
    with the partition axis.

    Unlike `build_traverse_fn_lanes` (single chip: CSR broadcast to every
    lane via `in_axes=(None, 0)`), the CSR blocks here are MESH-RESIDENT:
    their in_specs name the part axis, so device (l, p) reads partition
    p's adjacency out of its own HBM and never sees the other P-1 shards.
    The frontier is (L, P, vmax) sharded over BOTH axes — each device
    owns L/lanes query lanes of its partition's bitmap — and the per-hop
    bit-packed exchange is ONE `all_to_all` whose payload carries the
    full lanes × parts grid (`_exchange_marks_lanes`).

    The global result contract is IDENTICAL to `build_traverse_fn_lanes`:
    every leaf carries leading (L, P) axes (hop_edges (L, P, steps),
    cap arrays (L, P, nb, EB) / (L, P, steps, nb, EB), ...), so the
    runtime's `_escalate_lanes` / `_lane_attribution` de-mux paths work
    unchanged on either program.

    Degrade semantics: a (1, 1) mesh never reaches this builder (the
    runtime's local mode uses the vmap program), and a (1, P) mesh runs
    it with every lane resident on the part row — same program, lane
    axis unsplit.
    """
    ebs = _norm_ebs(EB, steps, capture_hops)
    # a MATCH program captures every hop as a frame; a GO its last hop
    cap_scope = "match/frame_capture" if capture_hops else "hop/capture"
    hubs_c, hub_owner, hub_local = _hub_consts(hub_dense, P)

    def kernel(blocks_data, frontier):
        fbm = frontier[:, 0]                   # (Ll, vmax) bool
        Ll = fbm.shape[0]
        vmax = fbm.shape[1]
        pid = jax.lax.axis_index("part").astype(jnp.int32)
        hop_edges: List[Any] = []
        frontier_sizes: List[Any] = []
        ovf_e = jnp.zeros((Ll,), bool)
        cap_out = None
        hop_caps: List[Dict[str, Any]] = []

        for hop in range(steps):
            frontier_sizes.append(jnp.sum(fbm, axis=1, dtype=jnp.int32))
            last = hop == steps - 1
            EBh = ebs[hop]
            marks = None                       # (Ll, P, vmax) bool
            edges_this_hop = jnp.zeros((Ll,), jnp.int32)
            caps = {"src": [], "dst": [], "rank": [], "eidx": [],
                    "kcount": []}
            efbm = fbm if hubs_c is None else _extend_fbm_sharded_lanes(
                fbm, pid, hub_owner, hub_local)
            for bi in range(n_blocks):
                b = blocks_data[bi]
                dcap = _delta_cap(b)
                dl = ({k: b[k][0] for k in
                       ("d_src", "d_dst", "d_rank", "d_valid", "d_tomb")}
                      if dcap else None)
                emax = b["nbr"].shape[-1]

                def lane_expand(f):
                    out = _expand_block(
                        b["indptr"][0], b["nbr"][0], b["rank"][0], f, EBh,
                        P, pid, vmax_local=vmax, hub_dense=hubs_c)
                    s, d, r, e, v, t, o = out
                    if dl is not None:
                        # per-lane merge: delta-row activity depends on
                        # THIS lane's frontier bitmap
                        s, d, r, e, v, t = _merge_delta(
                            dl, f, s, d, r, e, v, t, P, pid, emax)
                    return s, d, r, e, v, t, o

                src, dst, rk, eidx, ve, total, ovf = jax.vmap(
                    lane_expand)(efbm)
                ovf_e = ovf_e | ovf
                edges_this_hop = edges_this_hop + total

                def _col(name):
                    c = b["props"][name][0]
                    if dcap:
                        c = jnp.concatenate([c, b["d_props"][name][0]])
                    return c

                if pred is not None and (last or capture_hops):
                    cols = {"_rank": rk, "_src": src, "_dst": dst}
                    for name in pred_cols:
                        if not name.startswith("_"):
                            with jax.named_scope("hop/pred_gather"):
                                cols[name] = _col(name)[eidx]
                    with jax.named_scope("hop/predicate"):
                        keep = pred(cols) & ve
                else:
                    keep = ve
                if capture and (last or capture_hops):
                    with jax.named_scope(cap_scope):
                        cs, cd, cr, ce, kc = jax.vmap(
                            lambda s, d, r, e, k: _compact_cap(
                                s, d, r, e, k,
                                EBh + dcap))(src, dst, rk, eidx, keep)
                    caps["src"].append(cs)
                    caps["dst"].append(cd)
                    caps["rank"].append(cr)
                    caps["eidx"].append(ce)
                    caps["kcount"].append(kc)
                    if last and not capture_hops:
                        for name in yield_cols:
                            with jax.named_scope("hop/prop_" + name):
                                caps.setdefault("prop:" + name, []).append(
                                    _col(name)[ce])
                if not last:
                    marks_b = jax.vmap(
                        lambda d, k: _mark(d, k, P, vmax))(dst, keep)
                    marks = marks_b if marks is None else marks | marks_b
            hop_edges.append(edges_this_hop)
            if capture and (last or capture_hops):
                # arrays (Ll, nb, EB); kcount (Ll, nb)
                hop_caps.append({k: jnp.stack(v, axis=1)
                                 for k, v in caps.items()})

            if last:
                if capture:
                    if capture_hops:
                        with jax.named_scope("match/frame_stack"):
                            arr_keys = ("src", "dst", "rank", "eidx")
                            # local (Ll, 1, steps, nb, EB)
                            cap_out = {k: jnp.stack(
                                [hc[k] for hc in hop_caps], axis=1)[:, None]
                                for k in arr_keys}
                            kcount_out = jnp.stack(
                                [hc["kcount"] for hc in hop_caps],
                                axis=1)[:, None]
                    else:
                        cap_out = {k: v[:, None]
                                   for k, v in hop_caps[-1].items()
                                   if k != "kcount"}
                        kcount_out = hop_caps[-1]["kcount"][:, None]
                fbm = jnp.zeros((Ll, vmax), bool)
            else:
                fbm = _exchange_marks_lanes(marks, P, vmax)

        res = {
            "frontier": fbm[:, None],                       # (Ll, 1, vmax)
            "fcount": jnp.sum(fbm, axis=1, dtype=jnp.int32)[:, None],
            "hop_edges": jnp.stack(hop_edges, axis=1)[:, None],
            "frontier_sizes": jnp.stack(frontier_sizes, axis=1)[:, None],
            "ovf_expand": ovf_e[:, None],
        }
        if capture:
            res["cap"] = cap_out
            res["kcount"] = kcount_out
        return res

    from jax.sharding import PartitionSpec
    csr_spec = PartitionSpec("part")
    # legacy 1-D ('part',) meshes carry no lane axis: the global lane
    # dimension stays unsharded (every device holds all lanes) and the
    # same kernel runs with Ll == L
    lane_ax = "lane" if "lane" in mesh.axis_names else None
    lane_spec = PartitionSpec(lane_ax, "part")
    smapped = _shard_map(kernel, mesh=mesh,
                         in_specs=(csr_spec, lane_spec),
                         out_specs=lane_spec)
    return jax.jit(smapped)
