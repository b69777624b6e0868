"""Device delta-CSR: host-side dirty-key log + padded delta mirror.

The LSM split applied to device memory (ISSUE 19): the pinned base CSR
stays immutable while group-committed writes land in a bounded, padded
delta buffer — inserts union into every kernel's frontier expansion,
tombstones mask base edges — so an epoch bump costs one small
device_put instead of a graph-sized re-pin.  The same MemTable→SST
lineage as the reference's storage plane, shrunk to one mutable level.

Two host-side pieces live here (device placement is tpu/'s job):

* ``DeltaLog`` — a bounded *dirty-key* log attached to a space.  Write
  paths record WHICH edge/vertex keys changed, never row payloads; the
  apply step re-reads authoritative store state per key, which makes
  application idempotent and order-free (applying a superset of keys,
  or the same key twice, converges to the same mirror).  Structural
  changes that invalidate dense-id layout (REPARTITION, part installs,
  restore) mark the log broken → the next pin takes the full-rebuild
  path.

* ``HostDelta`` — the numpy mirror of the device delta buffers for one
  pinned snapshot: per (block, part) insert rows + tombstoned base edge
  indices, an ``apply()`` that folds dirty keys in by re-reading the
  store, and array builders that emit the padded (P, Dcap)/(P, Tcap)
  arrays the kernels consume.  Row encoding mirrors
  ``csr._build_block`` exactly (defaults, NULL sentinels, shared string
  pool) so merged results stay byte-identical to a full rebuild.

A compaction folds the mirror into a fresh base without asking the
store for anything: ``fold_base`` lays ``base − tombstones + rows`` of
a frozen copy of the mirror out in canonical CSR order, and
``HostDelta.adopt`` re-expresses, against that new base, the keys that
were applied while it was being built.  The new base plus its plane is
the old base plus its plane, edge for edge.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Set, Tuple

import numpy as np

from .csr import (INT_NULL, CsrSnapshot, _col_dtype, _encode_default,
                  encode_prop)

MAXI = np.iinfo(np.int32).max


def pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


class DeltaUnsupported(Exception):
    """This change set cannot ride the delta (unknown dense id, broken
    log, hub-split snapshot); the caller falls back to a full rebuild."""


class DeltaOverflow(Exception):
    """A (block, part) delta ran past its padded capacity (or a fresh
    vertex past the vmax slack); full rebuild folds everything in."""


class DeltaLog:
    """Bounded dirty-key log for one space.

    Keys are ``("e", etype, src_vid, dst_vid, rank)`` and
    ``("v", vid)`` — identity only, no payload.  ``note()`` is called
    by every write path while a device snapshot is watching; the store
    holds its own lock around calls, so the log needs none.

    Every note stamps its key with a new GENERATION, and ``trim()``
    drops a key only at the generation the apply was handed: a key
    noted again while an apply re-read it (a second writer, or the
    same write noted before it shipped and again once acknowledged)
    stays in the log for the next apply, whatever the first one saw.
    """

    __slots__ = ("floor_epoch", "keys", "broken", "cap", "part_epochs",
                 "_gen")

    def __init__(self, floor_epoch: int = 0, cap: int = 65536):
        self.floor_epoch = int(floor_epoch)
        self.cap = int(cap)
        # key → generation of its newest note
        self.keys: Dict[tuple, int] = {}
        self._gen = 0
        self.broken = False
        # cluster feed: highest store epoch seen in a write ack, per
        # part (the group-commit ack path carries it) — the coverage
        # check against live part stats at delta_records time
        self.part_epochs: Dict[int, int] = {}

    def note(self, key: tuple) -> None:
        if self.broken:
            return
        self._gen += 1
        self.keys[key] = self._gen
        if len(self.keys) > self.cap:
            self.broken = True

    def note_break(self) -> None:
        self.broken = True

    def note_epoch(self, pid: int, epoch: int) -> None:
        if epoch > self.part_epochs.get(pid, 0):
            self.part_epochs[pid] = epoch

    def records(self) -> Dict[tuple, int]:
        """The dirty keys now, each with its generation: what an apply
        folds in and hands back to ``trim()``."""
        return dict(self.keys)

    def trim(self, keys) -> None:
        """Drop the keys a successful delta apply consumed: `keys` is
        what ``records()`` handed it, and a key noted since (another
        generation) is kept."""
        for k, gen in keys.items():
            if self.keys.get(k) == gen:
                del self.keys[k]


@dataclass
class DeltaChanges:
    """What one apply() touched — the runtime re-puts exactly this."""
    blocks: Set[Tuple[str, str]] = field(default_factory=set)
    tag_cols: Set[Tuple[str, str]] = field(default_factory=set)
    num_vertices: bool = False
    dense_to_vid: bool = False

    def any(self) -> bool:
        return bool(self.blocks or self.tag_cols or self.num_vertices
                    or self.dense_to_vid)


def _enc_eq(a: Any, b: Any) -> bool:
    if isinstance(a, float) and isinstance(b, float):
        return a == b or (a != a and b != b)       # NaN == NaN here
    return a == b


def _rows_eq(a: Dict[str, Any], b: Dict[str, Any]) -> bool:
    if a.keys() != b.keys():
        return False
    return all(_enc_eq(v, b[k]) for k, v in a.items())


class HostDelta:
    """Host mirror of the device delta buffers for one snapshot."""

    def __init__(self, snap: CsrSnapshot, dcap: int, tcap: int = 0):
        self.snap = snap
        self.dcap = pow2(dcap)
        self.tcap = pow2(tcap or dcap)
        P = snap.num_parts
        # (etype, dir) → per-part OrderedDict
        #   (local_src, nbr_dense, rank) → {prop: encoded}
        self.ins: Dict[tuple, List[Dict[tuple, Dict[str, Any]]]] = {
            bk: [dict() for _ in range(P)] for bk in snap.blocks}
        # (etype, dir) → per-part set of tombstoned base edge indices
        self.tomb: Dict[tuple, List[Set[int]]] = {
            bk: [set() for _ in range(P)] for bk in snap.blocks}
        # per (etype,) cached encoded ALTER defaults keyed by prop name
        self._defaults: Dict[tuple, Dict[str, Any]] = {}
        # held by whoever mutates rows and tombstones (`apply`, `adopt`)
        # and by `freeze`, which copies them for a compaction's fold
        self.lock = threading.Lock()

    # -- occupancy -------------------------------------------------------

    def edges_per_part(self) -> List[int]:
        P = self.snap.num_parts
        out = [0] * P
        for per in self.ins.values():
            for p in range(P):
                out[p] += len(per[p])
        return out

    def tombs_per_part(self) -> List[int]:
        P = self.snap.num_parts
        out = [0] * P
        for per in self.tomb.values():
            for p in range(P):
                out[p] += len(per[p])
        return out

    def total_edges(self) -> int:
        return sum(self.edges_per_part())

    def total_tombs(self) -> int:
        return sum(self.tombs_per_part())

    def fill_ratio(self) -> float:
        """Worst (block, part) occupancy against the padded caps —
        the compaction watermark input."""
        worst = 0.0
        for bk in self.ins:
            for p in range(self.snap.num_parts):
                worst = max(worst,
                            len(self.ins[bk][p]) / self.dcap,
                            len(self.tomb[bk][p]) / self.tcap)
        return worst

    # -- encoding (parity with csr._build_block) -------------------------

    def _block_defaults(self, bk, sv) -> Dict[str, Any]:
        d = self._defaults.get(bk)
        if d is None:
            d = {}
            if sv is not None:
                for pd in sv.props:
                    d[pd.name] = _encode_default(pd, self.snap.pool)
            self._defaults[bk] = d
        return d

    def _encode_edge_row(self, bk, sv, row: Dict[str, Any]) -> Dict[str, Any]:
        blk = self.snap.blocks[bk]
        defaults = self._block_defaults(bk, sv)
        enc: Dict[str, Any] = {}
        for name, pt in blk.prop_types.items():
            fill = (np.nan if _col_dtype(pt) == np.float64 else INT_NULL)
            v = row.get(name)
            if v is None:
                a = defaults.get(name)
                enc[name] = fill if a is None else a
            else:
                enc[name] = encode_prop(pt, v, self.snap.pool)
        return enc

    def _base_eidx(self, bk, p: int, li: int, nbr_dense: int,
                   rank: int) -> Optional[int]:
        blk = self.snap.blocks[bk]
        if li + 1 >= blk.indptr.shape[1]:
            return None
        lo, hi = int(blk.indptr[p, li]), int(blk.indptr[p, li + 1])
        seg_n = blk.nbr[p, lo:hi]
        seg_r = blk.rank[p, lo:hi]
        w = np.nonzero((seg_n == nbr_dense) & (seg_r == rank))[0]
        return None if w.size == 0 else lo + int(w[0])

    def _base_row_eq(self, bk, p: int, eidx: int,
                     enc: Dict[str, Any]) -> bool:
        blk = self.snap.blocks[bk]
        for name, col in blk.props.items():
            if not _enc_eq(col[p, eidx].item(), enc[name]):
                return False
        return True

    # -- apply -----------------------------------------------------------

    def apply(self, reader, keys, changes: Optional[DeltaChanges] = None
              ) -> DeltaChanges:
        """Fold dirty keys into the mirror by re-reading store state
        through `reader` (edge_row / vertex_rows / dense_of).  Raises
        DeltaOverflow / DeltaUnsupported; the caller full-rebuilds."""
        ch = changes or DeltaChanges()
        if self.snap.hub_dense is not None:
            raise DeltaUnsupported("degree-split snapshot")
        with self.lock:
            for key in keys:
                if key[0] == "e":
                    self._apply_edge(reader, key, ch)
                elif key[0] == "v":
                    self._apply_vertex(reader, key[1], ch)
                else:
                    raise DeltaUnsupported(f"unknown delta key {key[0]!r}")
        self._held_to_caps(self.ins)
        return ch

    def _held_to_caps(self, blocks) -> None:
        for bk in blocks:
            for p in range(self.snap.num_parts):
                if len(self.ins[bk][p]) > self.dcap or \
                        len(self.tomb[bk][p]) > self.tcap:
                    raise DeltaOverflow(f"{bk} part {p}")

    def _apply_edge(self, reader, key, ch: DeltaChanges) -> None:
        _, etype, src, dst, rank = key
        row, sv = reader.edge_row(etype, src, dst, rank)
        sd_src = reader.dense_of(src)
        sd_dst = reader.dense_of(dst)
        if sd_src is None or sd_dst is None:
            if row is not None:
                raise DeltaUnsupported(f"no dense id for edge {key[1:]}")
            return                      # gone + never pinned: nothing to do
        P = self.snap.num_parts
        # an insert_edge can mint dense ids for endpoints that have no
        # vertex row (and thus no ("v",...) dirty key) — a rebuild would
        # still map them, so the mirror must too (materialize decodes
        # vids through snap.dense_to_vid)
        if row is not None:
            self._touch_dense(sd_src, src, ch)
            self._touch_dense(sd_dst, dst, ch)
        enc = None if row is None else \
            self._encode_edge_row((etype, "out"), sv, row)
        for (bk, p, li, nbr) in (
                ((etype, "out"), sd_src % P, sd_src // P, sd_dst),
                ((etype, "in"), sd_dst % P, sd_dst // P, sd_src)):
            if bk not in self.snap.blocks:
                continue                # edge type not exported: invisible
            if li >= self.snap.vmax:
                raise DeltaOverflow(f"local row {li} past vmax")
            if self._apply_half(bk, p, li, nbr, rank, enc):
                ch.blocks.add(bk)

    def _apply_half(self, bk, p: int, li: int, nbr: int, rank: int,
                    enc: Optional[Dict[str, Any]]) -> bool:
        ins = self.ins[bk][p]
        tomb = self.tomb[bk][p]
        k = (li, nbr, rank)
        base = self._base_eidx(bk, p, li, nbr, rank)
        changed = False
        if enc is None:                                     # edge absent
            if ins.pop(k, None) is not None:
                changed = True
            if base is not None and base not in tomb:
                tomb.add(base)
                changed = True
            return changed
        if base is not None and self._base_row_eq(bk, p, base, enc):
            # live base content already matches: drop any overrides
            # (covers tombstone-resurrect — delete then identical
            # re-insert unmask the base row instead of duplicating it)
            if ins.pop(k, None) is not None:
                changed = True
            if base in tomb:
                tomb.discard(base)
                changed = True
            return changed
        if base is not None and base not in tomb:
            tomb.add(base)
            changed = True
        cur = ins.get(k)
        if cur is None or not _rows_eq(cur, enc):
            ins[k] = enc
            changed = True
        return changed

    def _touch_dense(self, dense: int, vid, ch: DeltaChanges) -> None:
        """Make sure the snapshot maps `dense` → `vid` and the owning
        part's vertex count covers its local row (a rebuild would)."""
        snap = self.snap
        P = snap.num_parts
        p, li = dense % P, dense // P
        if li >= snap.vmax:
            raise DeltaOverflow(f"vertex local row {li} past vmax")
        changed = False
        if dense >= len(snap.dense_to_vid) or \
                snap.dense_to_vid[dense] is None:
            need = dense + 1 - len(snap.dense_to_vid)
            if need > 0:
                snap.dense_to_vid.extend([None] * need)
            snap.dense_to_vid[dense] = vid
            ch.dense_to_vid = True
            changed = True
        if li + 1 > int(snap.num_vertices[p]):
            snap.num_vertices[p] = li + 1
            ch.num_vertices = True
            changed = True
        if changed:
            self._kill_caches()

    def _kill_caches(self) -> None:
        # position/existence masks and the dense→vid decode array are
        # cached per snapshot object — a vertex change must kill them
        # (tpu/match_agg._exists_flat, assemble._d2v: the latter can go
        # stale WITHOUT a length change when a None slot gains a vid)
        for attr in ("_exists_flat", "_d2v_arr"):
            if hasattr(self.snap, attr):
                try:
                    delattr(self.snap, attr)
                except AttributeError:
                    pass

    def _apply_vertex(self, reader, vid, ch: DeltaChanges) -> None:
        snap = self.snap
        dense = reader.dense_of(vid)
        if dense is None:
            raise DeltaUnsupported(f"no dense id for vertex {vid!r}")
        P = snap.num_parts
        p, li = dense % P, dense // P
        self._touch_dense(dense, vid, ch)
        rows = reader.vertex_rows(vid)
        for tag, tt in snap.tags.items():
            row = rows.get(tag)
            sv = reader.tag_schema(tag)
            if row is None:
                if tt.present[p, li]:
                    tt.present[p, li] = False
                    ch.tag_cols.add((tag, "present"))
                for name, pt in tt.prop_types.items():
                    fill = (np.nan
                            if _col_dtype(pt) == np.float64 else INT_NULL)
                    if not _enc_eq(tt.props[name][p, li].item(), fill):
                        tt.props[name][p, li] = fill
                        ch.tag_cols.add((tag, name))
                continue
            if not tt.present[p, li]:
                tt.present[p, li] = True
                ch.tag_cols.add((tag, "present"))
            defaults = self._block_defaults(("tag", tag), sv)
            for name, pt in tt.prop_types.items():
                fill = (np.nan
                        if _col_dtype(pt) == np.float64 else INT_NULL)
                v = row.get(name)
                if v is None:
                    a = defaults.get(name)
                    env = fill if a is None else a
                else:
                    env = encode_prop(pt, v, snap.pool)
                if not _enc_eq(tt.props[name][p, li].item(), env):
                    tt.props[name][p, li] = env
                    ch.tag_cols.add((tag, name))
        self._kill_caches()

    # -- compaction ------------------------------------------------------

    def freeze(self):
        """(rows, tombstones) as they stand between two applies, per
        (block, part): what ``fold_base`` folds in.  A row's encoded
        dict is replaced, never mutated, so the copies are shallow."""
        with self.lock:
            return ({bk: [dict(d) for d in per]
                     for bk, per in self.ins.items()},
                    {bk: [set(t) for t in per]
                     for bk, per in self.tomb.items()})

    def content(self, bk, p: int, li: int, nbr: int,
                rank: int) -> Optional[Dict[str, Any]]:
        """What base plus plane hold for one edge half: its encoded
        row, or None where it is absent."""
        enc = self.ins[bk][p].get((li, nbr, rank))
        if enc is not None:
            return enc
        base = self._base_eidx(bk, p, li, nbr, rank)
        if base is None or base in self.tomb[bk][p]:
            return None
        blk = self.snap.blocks[bk]
        return {name: col[p, base].item() for name, col in blk.props.items()}

    def adopt(self, old: "HostDelta", keys, dense_of) -> Set[Tuple[str, str]]:
        """Carry into this (fresh) mirror what `old` holds for the edge
        `keys`, relative to THIS mirror's base: the catch-up of a
        compaction whose base was folded from a frozen copy of `old`
        while `keys` were still being applied to it.  Reads nothing
        from the store.  Vertex keys need nothing: a folded base shares
        its vertex tables, counts and dictionary with the old one.
        Returns the blocks that changed; raises like ``apply``."""
        P = self.snap.num_parts
        changed: Set[Tuple[str, str]] = set()
        for key in keys:
            if key[0] != "e":
                continue
            _, etype, src, dst, rank = key
            s, d = dense_of(src), dense_of(dst)
            if s is None or d is None:
                continue                # never pinned, never applied
            for bk, p, li, nbr in (((etype, "out"), s % P, s // P, d),
                                   ((etype, "in"), d % P, d // P, s)):
                if bk not in self.ins or li >= self.snap.vmax:
                    continue
                if self._apply_half(bk, p, li, nbr, rank,
                                    old.content(bk, p, li, nbr, rank)):
                    changed.add(bk)
        self._held_to_caps(changed)
        return changed

    # -- padded arrays (host copies; the runtime device_puts them) -------

    def block_arrays(self, bk) -> Dict[str, Any]:
        snap = self.snap
        P = snap.num_parts
        blk = snap.blocks[bk]
        d_src = np.zeros((P, self.dcap), np.int32)
        d_dst = np.zeros((P, self.dcap), np.int32)
        d_rank = np.zeros((P, self.dcap), np.int32)
        d_valid = np.zeros((P, self.dcap), bool)
        d_props: Dict[str, np.ndarray] = {}
        for name, pt in blk.prop_types.items():
            dt = _col_dtype(pt)
            fill = np.nan if dt == np.float64 else INT_NULL
            d_props[name] = np.full((P, self.dcap), fill, dt)
        d_tomb = np.full((P, self.tcap), MAXI, np.int32)
        for p in range(P):
            for j, ((li, nbr, rank), enc) in \
                    enumerate(self.ins[bk][p].items()):
                d_src[p, j] = li
                d_dst[p, j] = nbr
                d_rank[p, j] = rank
                d_valid[p, j] = True
                for name in d_props:
                    d_props[name][p, j] = enc[name]
            ts = sorted(self.tomb[bk][p])
            if ts:
                d_tomb[p, :len(ts)] = np.asarray(ts, np.int32)
        return {"d_src": d_src, "d_dst": d_dst, "d_rank": d_rank,
                "d_valid": d_valid, "d_tomb": d_tomb, "d_props": d_props}

    def nbytes(self) -> int:
        total = 0
        for bk in self.snap.blocks:
            blk = self.snap.blocks[bk]
            per_row = 4 * 3 + 1 + sum(
                np.dtype(_col_dtype(pt)).itemsize
                for pt in blk.prop_types.values())
            total += self.snap.num_parts * (
                self.dcap * per_row + self.tcap * 4)
        return total


def padded_width(rows: int, cap: int) -> int:
    """A part's padded edge width for `rows` live slots under a delta
    plane of `cap` edges a (block, part): at least `cap` free slots,
    rounded up to a multiple of `cap`.  A compaction folds at most
    `cap` rows into a part, so the first fold of a pinned base always
    fits the width its programs were compiled for."""
    cap = max(int(cap), 1)
    return -(-(int(rows) + cap) // cap) * cap


def _resized(a: np.ndarray, width: int, fill) -> np.ndarray:
    """A new array of `a`'s rows at `width` slots (never narrower than
    `a`), the slots past `a`'s own filled."""
    width = max(width, a.shape[-1])
    out = np.full(a.shape[:-1] + (width,), fill, a.dtype)
    out[..., :a.shape[-1]] = a
    return out


def _prop_fill(dtype):
    return np.nan if dtype == np.float64 else INT_NULL


def pad_edge_width(snap: CsrSnapshot, cap: int) -> CsrSnapshot:
    """Widen every block of `snap` (in place) to ``padded_width`` of
    its fullest part: the slack a compaction folds into.  Pad slots
    read as any other slot past a part's last row."""
    for blk in snap.blocks.values():
        width = padded_width(int(blk.indptr[:, -1].max(initial=0)), cap)
        blk.nbr = _resized(blk.nbr, width, -1)
        blk.rank = _resized(blk.rank, width, 0)
        for name, col in blk.props.items():
            blk.props[name] = _resized(col, width, _prop_fill(col.dtype))
    return snap


def _nbr_keyer(snap: CsrSnapshot):
    """-> keys(nbr): the neighbour ordering key of
    ``native.kernels.dst_sort_key`` for dense ids: the vid itself for
    int vids, code-point string order otherwise; a neighbour without a
    dense id keeps its place among equals (the sort is stable)."""
    d2v = snap.dense_to_vid
    sample = next((v for v in d2v if v is not None), None)
    if isinstance(sample, int) and not isinstance(sample, bool):
        table = np.asarray([-1 if v is None else v for v in d2v], np.int64)
        none = -1
    else:
        table = np.asarray(["" if v is None else str(v) for v in d2v],
                           dtype="U")
        none = ""

    def keys(nbr: np.ndarray) -> np.ndarray:
        ok = (nbr >= 0) & (nbr < table.size)
        return np.where(ok, table[np.where(ok, nbr, 0)], none)
    return keys


def fold_base(snap: CsrSnapshot, ins, tomb, cap: int) -> CsrSnapshot:
    """A fresh base that holds `snap`'s edges minus the tombstoned
    slots plus the plane's rows (`ins`, `tomb`: ``HostDelta.freeze()``),
    each part in the canonical CSR order a rebuild would give (local
    row, rank, neighbour key).  Nothing is read from the store, dense
    ids keep their meaning, and the vertex side (tag tables, counts,
    dictionary, string pool) is SHARED with `snap`: vertex changes are
    applied to it in place, so the two never differ there.  A block
    keeps its padded width while its fullest part fits, and grows to
    ``padded_width`` where it does not.  The epoch is `snap`'s: the
    programs compiled over it stay valid over the new base."""
    P = snap.num_parts
    out = CsrSnapshot(space=snap.space, epoch=snap.epoch, num_parts=P,
                      vmax=snap.vmax, num_vertices=snap.num_vertices,
                      tags=snap.tags, pool=snap.pool,
                      dense_to_vid=snap.dense_to_vid,
                      hub_dense=snap.hub_dense)
    keys = None
    for bk, blk in snap.blocks.items():
        counts = [int(blk.indptr[p, -1]) - len(tomb[bk][p])
                  + len(ins[bk][p]) for p in range(P)]
        width = blk.nbr.shape[1]
        if max(counts, default=0) > width:
            width = padded_width(max(counts), cap)
        indptr = blk.indptr.copy()
        nbr = _resized(blk.nbr, width, -1)
        rank = _resized(blk.rank, width, 0)
        props = {n: _resized(c, width, _prop_fill(c.dtype))
                 for n, c in blk.props.items()}
        for p in range(P):
            rows, dead = ins[bk][p], tomb[bk][p]
            if not rows and not dead:
                continue
            n0 = int(blk.indptr[p, -1])
            keep = np.ones(n0, bool)
            if dead:
                keep[np.fromiter(dead, np.int64, len(dead))] = False
            local = np.repeat(np.arange(snap.vmax, dtype=np.int64),
                              np.diff(blk.indptr[p]))[keep]
            new = list(rows.items())
            local = np.concatenate(
                [local, np.asarray([k[0] for k, _ in new], np.int64)])
            nb = np.concatenate(
                [blk.nbr[p, :n0][keep],
                 np.asarray([k[1] for k, _ in new], blk.nbr.dtype)])
            rk = np.concatenate(
                [blk.rank[p, :n0][keep],
                 np.asarray([k[2] for k, _ in new], blk.rank.dtype)])
            keys = keys or _nbr_keyer(snap)
            order = np.lexsort((keys(nb), rk, local))
            n1 = order.size
            nbr[p, :n1], nbr[p, n1:] = nb[order], -1
            rank[p, :n1], rank[p, n1:] = rk[order], 0
            for name, col in blk.props.items():
                vals = np.concatenate(
                    [col[p, :n0][keep],
                     np.asarray([enc[name] for _, enc in new], col.dtype)])
                props[name][p, :n1] = vals[order]
                props[name][p, n1:] = _prop_fill(col.dtype)
            indptr[p, 0] = 0
            np.cumsum(np.bincount(local, minlength=snap.vmax)[:snap.vmax],
                      out=indptr[p, 1:])
        out.blocks[bk] = type(blk)(
            etype=blk.etype, direction=blk.direction, indptr=indptr,
            nbr=nbr, rank=rank, props=props,
            prop_types=dict(blk.prop_types))
    return out


class LocalStoreReader:
    """Re-read adapter over a single-process GraphStore (under sd.lock
    at the call site: apply runs with the gate's write side held, so
    reads here see a consistent post-commit state)."""

    def __init__(self, store, space: str):
        self.store = store
        self.space = space
        self.sd = store.space(space)
        import time as _t
        self.now = _t.time()

    def dense_of(self, vid) -> Optional[int]:
        d = self.sd.vid_to_dense.get(vid)
        return None if d is None else int(d)

    def edge_row(self, etype, src, dst, rank):
        from .schema import SchemaError
        from .store import ttl_expired
        try:
            sv = self.store.catalog.get_edge(self.space, etype).latest
        except SchemaError:
            return None, None           # dropped edge type: invisible
        row = self.sd.parts[self.sd.part_of(src)].out_edges \
            .get(src, {}).get(etype, {}).get((rank, dst))
        if row is None:
            return None, sv
        if sv.ttl_col and sv.ttl_duration > 0 and \
                ttl_expired(sv, row, self.now):
            return None, sv
        return row, sv

    def vertex_rows(self, vid) -> Dict[str, Dict[str, Any]]:
        from .store import ttl_expired
        tv = self.sd.parts[self.sd.part_of(vid)].vertices.get(vid) or {}
        out = {}
        for tag, (_ver, row) in tv.items():
            sv = self.tag_schema(tag)
            if sv is None:
                continue
            if sv.ttl_col and sv.ttl_duration > 0 and \
                    ttl_expired(sv, row, self.now):
                continue
            out[tag] = row
        return out

    def tag_schema(self, tag):
        from .schema import SchemaError
        try:
            return self.store.catalog.get_tag(self.space, tag).latest
        except SchemaError:
            return None
