"""TpuRuntime: snapshot pinning lifecycle + traversal dispatch.

Owns the mesh, the per-space DeviceSnapshots (epoch-checked against the
host store: a write bumps the space epoch, the next traversal re-pins —
the serve-epoch-N-while-building-N+1 model of SURVEY §7 hard-part #6 in
its simplest correct form), the jit cache keyed by bucket configuration,
and the power-of-two escalation loop around the hop kernel.

The host materialization contract: the device returns (src, dst, eidx,
and rank where something reads it) per block, kept entries compacted to
a prefix; property decode happens on host straight out of
the numpy CsrSnapshot columns at eidx — properties cross HBM only when
a predicate needs them.
"""
from __future__ import annotations

import functools
import logging
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor, wait as futures_wait
from contextlib import contextmanager, nullcontext
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..core import expr as E
from ..core.value import ColumnarDataSet, Edge
from ..graphstore.csr import (NUMERIC_KINDS, build_snapshot,
                              decode_prop_column, decode_prop_column_np)
from ..graphstore.delta import (DeltaOverflow, DeltaUnsupported, HostDelta,
                                fold_base, pad_edge_width,
                                pow2 as _delta_pow2)
from ..graphstore.store import GraphStore
from ..native.kernels import join_halves as native_join_halves
from ..utils import trace as _t
from ..utils.config import get_config
from ..utils.stats import stats as _metrics
from .device import (DeviceDelta, DeviceSnapshot, SnapshotRetired,
                     TpuUnavailable, make_mesh, mesh_lanes, mesh_parts, note_host_fallback,
                     pin_snapshot, put_delta_blocks)
from .exprjit import (CannotCompile, compile_predicate, eval_yield_column,
                      eval_yield_column_np)
from .hop import a2a_payload_bytes, build_traverse_fn


_log = logging.getLogger(__name__)


def _on_live_snapshot(fn):
    """The entry of a device statement (`traverse`, `traverse_hops`,
    `bfs`).  One that met a swap is served from the snapshot that
    replaced its own: `SnapshotRetired` (raised under the read gate,
    before anything ran) pins again and runs the statement anew.  Only a
    space that keeps being replaced under one statement (a few times
    over) is handed to the caller's fallback.

    A statement that arrives with no trace active (an embedded runtime:
    `pin_prebuilt`, the tools, the benchmark's proxy cells) is rooted
    HERE, around its retries too, as `query:tpu.<entry>`: the spans
    below are then live, fold into the phase ledger when the root
    closes and reach `/traces`.  Under graphd the statement's root is
    active and none is opened; the flag is the one graphd's root obeys."""
    entry = fn.__name__

    def attempts(self, *args, **kw):
        for _ in range(self.RETIRED_RETRIES):
            try:
                return fn(self, *args, **kw)
            except SnapshotRetired:
                _metrics().inc("tpu_stmt_retired_retries")
        return fn(self, *args, **kw)

    @functools.wraps(fn)
    def run(self, store, space, *args, **kw):
        if _t.current_ctx() is not None or \
                not get_config().get("enable_query_tracing"):
            return attempts(self, store, space, *args, **kw)
        with _t.start_trace(f"query:tpu.{entry}", service="tpu", space=space):
            return attempts(self, store, space, *args, **kw)
    return run


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


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


# Row assembly hands a LARGE statement's piece-passes to a few threads:
# a pass writes a disjoint slice of its column, the native join holds no
# GIL and neither does numpy's typed copy, so the pieces of a block's
# columns (parts x columns of them) are independent tasks.  Under
# POOL_MIN_ROWS kept rows the handoff costs more than it buys and the
# serial passes run (PERF.md section 6, PR 40, has the sweep on the
# chip's host).  One pool a process, made at the first statement that
# needs it; none on a host with one core.
POOL_MIN_ROWS = 1 << 20
_POOL_WIDTH = min(4, os.cpu_count() or 1)
_pool_lock = threading.Lock()
_pool: Optional[ThreadPoolExecutor] = None


def _assembly_pool() -> Optional[ThreadPoolExecutor]:
    global _pool
    if _pool is None and _POOL_WIDTH > 1:
        with _pool_lock:
            if _pool is None:
                _pool = ThreadPoolExecutor(_POOL_WIDTH,
                                           thread_name_prefix="tpu-mat")
    return _pool


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


class _DispatchGate:
    """Read-write gate serializing device dispatch against snapshot
    re-pin (ISSUE 9 satellite: the serve-while-repin fix).

    jaxlib's CPU client has a latent race where concurrent jitted
    dispatches can deadlock against a device_put re-pinning a bumped
    epoch (CHANGES.md PR 6 note: both reader threads blocked inside
    the jitted call, no Python-level locks held).  Dispatches are
    READERS — they share, so concurrent queries still overlap on the
    chip — and a re-pin is the WRITER: it waits for in-flight
    dispatches to drain and excludes new ones while the put runs.
    Writer preference (a waiting writer blocks NEW readers) so a
    steady dispatch stream cannot starve the epoch bump forever.

    acquire_* returns the seconds spent waiting — the dispatch side's
    wait is the statement's queue time (tpu_dispatch_queue_us)."""

    __slots__ = ("_cond", "_readers", "_writer", "_writers_waiting")

    def __init__(self):
        self._cond = threading.Condition()
        self._readers = 0
        self._writer = False
        self._writers_waiting = 0

    def acquire_read(self) -> float:
        t0 = time.perf_counter()
        with self._cond:
            while self._writer or self._writers_waiting:
                self._cond.wait()
            self._readers += 1
        return time.perf_counter() - t0

    def release_read(self):
        with self._cond:
            self._readers -= 1
            if self._readers == 0:
                self._cond.notify_all()

    def acquire_write(self) -> float:
        t0 = time.perf_counter()
        with self._cond:
            self._writers_waiting += 1
            try:
                while self._writer or self._readers:
                    self._cond.wait()
                self._writer = True
            finally:
                self._writers_waiting -= 1
        return time.perf_counter() - t0

    def release_write(self):
        with self._cond:
            self._writer = False
            self._cond.notify_all()

    def write_held(self) -> bool:
        """True while a writer holds or waits for the gate — the batch
        former's probe (ISSUE 19 satellite): a formed multi-lane batch
        would otherwise queue its whole batch_wait_us budget behind the
        writer, so the former re-arms its window instead."""
        with self._cond:
            return bool(self._writer or self._writers_waiting)


# Result keys of a traverse program (hop.py `_traverse`) that say how far
# its by-need loops and member plans engaged, lead + (steps,); summed
# they are the TraverseStats fields of the same names.  A BFS program
# (bfs.py) returns the first two for its level loops.
_ENGAGEMENT = ("chunks_run", "chunks_budget", "plan_run", "plan_budget")

# The result leaves some caller reads, the only ones `_fetch` brings to
# the host besides the capture: the ladder's counts and flags, the kept
# counts, the work counters, BFS's depths and the direction each of its
# levels took.  The post-final `frontier` bitmap and its `fcount` stay on
# the device and die with the rung (vmax bools a part: more bytes than a
# mean four-chip statement's rows).
_FETCHED = ("hop_edges", "ovf_expand", "kcount", "frontier_sizes",
            "dist", "bottom_up") + _ENGAGEMENT

# How a capture leaves the device (`_fetch`).  A ROW is one index of a
# capture array's lead + (nb,) axes with its own kept count; kept
# entries are a prefix of their row (hop.py `_compact_cap`), so only
# prefixes are shipped, cut by programs that depend on the capture's
# shape and the columns read alone and are compiled when the traverse
# program first runs for those columns (`TpuRuntime._warm_fetch`),
# never when a kept size is first met.
#
# W < 2 * SLICE_MAX, i.e. a hop budget of at most SLICE_MAX (the served
# statements' 8,192 and 65,536 slots; budgets are powers of two, and an
# armed delta plane's tail widens a capture by less than its budget, so
# the plane never moves a capture to the other taker): ONE slice of
# every row, `v[..., :k]`, k a power of two from SLICE_MIN up (the
# whole width last), speculated from the program's last run.
#
# Wider: every row apart, in pieces cut on the device that holds it, so
# the bytes follow each row's own count (not the fullest row's, rounded
# up, for all).  What the chips charged when the constants below were
# settled (PERF.md section 6, PR 31, has the tables): a piece 0.6 ms on
# the four-chip host (its launch and a transfer a column) to 3 ms on
# one chip however small it is (there most of it the split of a whole
# 64-bit operand before the slice, which no capture holds since PR 35:
# a property column is its 32-bit halves, `(2, size)` a piece), a
# byte 0.3 to 0.5 ns, and a second round trip waits behind whatever
# another session has on the chips.  Hence: a row comes in ONE piece of
# the smallest of PIECES that holds it unless a second piece saves
# PIECE_WORTH slots; and a row that last kept at most SPEC_ROWS comes
# speculatively, one piece of at most SPEC_SLOTS, with the meta (the
# median statement of the four-chip cell then makes one round trip),
# while a longer row is not guessed at (a wrong guess would cost more
# than the round trip, a hundredth of its transfer).
SLICE_MIN, SLICE_MAX = 1 << 7, 1 << 16
PIECES = tuple(1 << i for i in range(11, 22))
PIECE_WORTH = 1 << 16
SPEC_SLOTS, SPEC_ROWS = 1 << 15, 1 << 17


@functools.partial(jax.jit, static_argnames="k")
def _head(cap, k: int):
    return {n: v[..., :k] for n, v in cap.items()}


@functools.partial(jax.jit, static_argnames="size")
def _piece(cap, at, size: int):
    """`size` slots of one row of each of these capture columns, from
    `at` = the row's index and the first slot, both traced: one
    executable a capture shape, column set and size.  A start past
    W - size is clamped to it (`lax.dynamic_slice`).  A column comes
    flat, `(size,)`; a property column (one axis more, its halves,
    before the slots) as `(2, size)`: ONE array and one transfer a
    column either way."""
    row = [at[i] for i in range(at.shape[0] - 1)]

    def cut(v):
        halves = (2,) * (v.ndim - at.shape[0])
        return jax.lax.dynamic_slice(
            v, row + [jnp.int32(0)] * len(halves) + [at[-1]],
            (1,) * len(row) + halves + (size,)).reshape(halves + (size,))
    return {n: cut(v) for n, v in cap.items()}


def _nbytes(tree) -> int:
    return sum(a.nbytes for a in jax.tree.leaves(tree))


def _taker(cap_dev, want=None):
    """The way this capture's rows leave the device, by its width; of
    its columns the host takes those in `want` (all of them if None)."""
    wide = next(iter(cap_dev.values())).shape[-1] >= 2 * SLICE_MAX
    return (_Pieces if wide else _Heads)(cap_dev, want)


class _Heads:
    """The kept prefixes of a capture whose hop budget is at most
    SLICE_MAX slots (narrower than twice that with a delta plane's
    tail), as one slice of every row.  `speculate(counts)` and `ask(counts)`
    return the device arrays that cover rows of these kept counts (the
    last run's, this run's), or None where there is nothing to ask for
    beyond what was asked before; `got` takes them once on the host;
    `rows` is the fetched capture: per column an object array over the
    rows, of each row's pieces in slot order, trimmed to its kept
    count (a property column's pieces are its halves, `(2, n)`, which
    `_join_halves` joins).  The programs run over the wanted columns
    together (one launch, not one a column), so they are compiled for a
    program's key AND the columns its statement reads
    (`TpuRuntime._warm_fetch`)."""

    def __init__(self, cap_dev, want=None):
        self.dev = cap_dev
        self.want = [n for n in cap_dev if want is None or n in want]
        self.W = next(iter(cap_dev.values())).shape[-1]
        # the axes that index a row, lead + (nb,): all but the slots,
        # and but a property column's halves
        self.nrow = min(v.ndim for v in cap_dev.values()) - 1
        self.k = 0
        self.host: Dict[str, np.ndarray] = {}
        self.nbytes = 0

    def item_bytes(self) -> int:
        """Bytes of one kept entry over the wanted columns (a property
        column's two halves: 8)."""
        return sum(self.dev[n].dtype.itemsize * (self.dev[n].ndim - self.nrow)
                   for n in self.want)

    def _k(self, n: int) -> int:
        return min(self.W, max(SLICE_MIN, _pow2(n)))

    def warm(self):
        cols = {n: self.dev[n] for n in self.want}
        for k in sorted({self._k(1 << i)
                         for i in range(self.W.bit_length() + 1)}):
            _head(cols, k)

    def ask(self, counts):
        k = self._k(int(np.max(counts, initial=0)))
        if k <= self.k:
            return None
        self.k = k
        return _head({n: self.dev[n] for n in self.want}, k)

    speculate = ask

    def got(self, host):
        self.host = host
        self.nbytes += _nbytes(host)

    def rows(self, kc):
        cap = {}
        for n, a in self.host.items():
            col = cap[n] = np.empty(kc.shape, object)
            for idx in np.ndindex(kc.shape):
                col[idx] = [a[idx][..., :kc[idx]]]
        return cap


class _Pieces(_Heads):
    """The same of a wider capture, every row in pieces cut on the
    device that holds it: a sharded column is read shard by shard
    (`addressable_shards`), so no slice crosses chips and `device_get`
    assembles nothing.  `ask` cuts only what lies past the pieces
    already asked for: an undershot speculation fetches a tail, never
    the prefix again."""

    def __init__(self, cap_dev, want=None):
        super().__init__(cap_dev, want)
        # where its rows lie in the whole -> a shard's wanted columns
        self.shards: Dict[Tuple, Dict[str, Any]] = {}
        for n in self.want:
            for s in cap_dev[n].addressable_shards:
                if s.replica_id == 0:
                    base = tuple(sl.start or 0
                                 for sl in s.index[:self.nrow])
                    self.shards.setdefault(base, {})[n] = s.data
        self.sizes = [c for c in PIECES if c <= self.W]
        self.have: Dict[Tuple, int] = {}    # row -> slots asked for
        # of each piece asked for: its row, and that it holds the row's
        # slots [slot, slot + c) from its own `skip` on
        self.asked: List[Tuple] = []
        self.host: List[Dict[str, np.ndarray]] = []

    def _size(self, n: int) -> int:
        """The smallest piece that holds n slots (the largest if none)."""
        return next((c for c in self.sizes if c >= n), self.sizes[-1])

    def _cut(self, out, cols, idx, row, slot, c):
        start = min(slot, self.W - c)
        out.append(_piece(cols, np.asarray(idx + (start,), np.int32), c))
        self.asked.append((row, slot, slot - start, c))
        self.have[row] = slot + c

    def _rows(self):
        for base, cols in self.shards.items():
            lead = next(iter(cols.values())).shape[:self.nrow]
            for idx in np.ndindex(lead):
                yield cols, idx, tuple(b + i for b, i in zip(base, idx))

    def warm(self):
        at = np.zeros(self.nrow + 1, np.int32)
        for cols in self.shards.values():
            for c in self.sizes:
                _piece(cols, at, c)

    def speculate(self, counts):
        counts = np.broadcast_to(counts, next(
            iter(self.dev.values())).shape[:self.nrow])
        out = []
        for cols, idx, row in self._rows():
            if 0 < counts[row] <= SPEC_ROWS:
                self._cut(out, cols, idx, row, 0,
                          self._size(min(int(counts[row]), SPEC_SLOTS)))
        return out or None

    def ask(self, counts):
        out = []
        for cols, idx, row in self._rows():
            slot, kept = self.have.get(row, 0), int(counts[row])
            while slot < kept:
                c = self._size(kept - slot)
                half = c // 2
                if half in self.sizes and kept - slot > half and \
                        half - self._size(kept - slot - half) >= PIECE_WORTH:
                    c = half
                self._cut(out, cols, idx, row, slot, c)
                slot += c
        return out or None

    def got(self, host):
        self.host.extend(host)
        self.nbytes += _nbytes(host)

    def rows(self, kc):
        cap = {n: np.empty(kc.shape, object) for n in self.want}
        for col in cap.values():
            for row in np.ndindex(kc.shape):
                col[row] = []
        for (row, slot, skip, c), piece in zip(self.asked, self.host):
            end = skip + min(c, int(kc[row]) - slot)
            if end > skip:
                for n, col in cap.items():
                    col[row].append(piece[n][..., skip:end])
        return cap


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


class TpuRuntime:
    """One per process; holds the mesh and all pinned spaces."""

    def __init__(self, mesh=None, n_devices: Optional[int] = None):
        self.mesh = mesh if mesh is not None else make_mesh(n_devices)
        self.mesh_size = mesh_parts(self.mesh)
        self.mesh_lanes = mesh_lanes(self.mesh)
        self.local_mode = self.mesh_size == 1
        # bumped by set_mesh: part of every lane-batch compatibility key
        # so lanes compiled for different launch grids never merge
        # (PR 12 composition fix)
        self._mesh_epoch = 0
        self.snapshots: Dict[str, DeviceSnapshot] = {}
        # program key → (the program, bytes of its 64-bit operands)
        self._fns: Dict[Tuple, Any] = {}
        # program key → last kept-prefix fetch size: arms the
        # speculative single-phase result fetch (one device round trip
        # instead of two for repeat query shapes); in-memory only
        self._kmax: Dict[Tuple, int] = {}
        # (program key, columns fetched) whose fetch programs are
        # compiled (`_warm_fetch`); pruned with _kmax
        self._fetch_warm: set = set()
        # seed-bitmap builder programs (bounded separately from _fns:
        # space-keyed pruning does not reach these target/vmax keys) and
        # the (key, pad bucket) pairs already compiled — the warm call
        # runs outside put_s so the metric stays transfer-only
        self._seed_fns: Dict[Tuple, Any] = {}
        self._seed_warm: set = set()
        # program → last converged (0, EB): repeat queries start AT the
        # converged bucket instead of re-climbing the escalation ladder
        # (the ladder re-runs the kernel once per rung, per query).
        # Value stays a 2-tuple for cache-file compat; slot 0 (the old
        # frontier bucket F) is always 0 with the bitmap frontier.
        self._buckets: Dict[Tuple, Tuple[int, int]] = {}
        # optional cross-process persistence (NEBULA_BUCKET_CACHE=path):
        # each escalation rung is a fresh XLA compile — a caller that
        # opts in starts a repeat run at the previously converged
        # sizes instead of re-climbing
        import os as _os
        self._buckets_path = _os.environ.get("NEBULA_BUCKET_CACHE")
        if self._buckets_path:
            try:
                import ast as _ast
                import json as _json
                with open(self._buckets_path) as f:
                    # keys are repr'd tuples of primitives; literal_eval
                    # (never eval/pickle — the path is configurable)
                    self._buckets = {_ast.literal_eval(k): tuple(v)
                                     for k, v in _json.load(f).items()}
            except Exception:  # noqa: BLE001 — absent/corrupt cache
                self._buckets = {}
        self.max_retries = 10
        # dispatch-vs-repin gate (ISSUE 9): dispatches share, re-pins
        # exclude — see _DispatchGate
        self._gate = _DispatchGate()
        # collective-launch mutex (PR 17): a sharded program carries
        # all_to_all/psum rendezvous over the mesh; two such programs
        # running CONCURRENTLY on overlapping devices interleave their
        # rendezvous and deadlock (observed on the CPU virtual mesh,
        # same hazard on real ICI).  Local-mode programs are
        # collective-free and keep full dispatch concurrency.
        self._launch_mutex = threading.Lock()
        # the bitmap frontier (round-4 redesign) has no size bucket;
        # the only escalating budget left is the per-block edge budget
        self.init_eb = int(get_config().get("tpu_init_edge_budget"))
        self.max_cap = 1 << 24          # escalation sanity bound

    # -- pinning ----------------------------------------------------------

    def _mesh_key(self) -> Tuple[int, int, int]:
        """(lanes, parts, epoch): the launch-grid identity every
        lane-batch compatibility key and bench A/B must carry."""
        return (self.mesh_lanes, self.mesh_size, self._mesh_epoch)

    def set_mesh(self, mesh) -> None:
        """Swap the runtime onto a different mesh (bench A/B, elastic
        re-shard).  Runs under the WRITE side of the dispatch gate:
        in-flight dispatches drain, every pinned snapshot's buffers are
        donated back (they are laid out for the OLD grid), the jit and
        seed caches drop, and the mesh epoch bumps so any batch group
        still forming against the old grid can never merge with lanes
        compiled for the new one."""
        self._gate.acquire_write()
        try:
            for dev in self.snapshots.values():
                dev.delete_buffers()
            self.snapshots.clear()
            self._fns.clear()
            self._kmax.clear()
            self._fetch_warm.clear()
            self._seed_fns.clear()
            self._seed_warm.clear()
            self.mesh = mesh
            self.mesh_size = mesh_parts(mesh)
            self.mesh_lanes = mesh_lanes(mesh)
            self.local_mode = self.mesh_size == 1
            self._mesh_epoch += 1
        finally:
            self._gate.release_write()
        self._emit_hbm_gauges()

    def _emit_hbm_gauges(self) -> None:
        """Re-state the HBM residency gauges: the total plus the
        per-shard ledger (`tpu_shard_hbm_bytes{shard}` summed over every
        pinned space) and the mesh width (`tpu_shards`).  Stale shard
        slots from a wider previous mesh are zeroed, not dropped —
        last-write-wins gauges would otherwise report a ghost shard."""
        from ..utils.stats import stats
        per: Dict[int, int] = {}
        for dev in self.snapshots.values():
            for p, b in dev.shard_hbm_bytes().items():
                per[p] = per.get(p, 0) + b
        st = stats()
        st.gauge("tpu_hbm_bytes_pinned", float(sum(per.values())))
        st.gauge("tpu_shards", float(self.mesh_size))
        known = st.labeled_gauges.get("tpu_shard_hbm_bytes", {})
        for p in range(self.mesh_size):
            st.gauge_labeled("tpu_shard_hbm_bytes", {"shard": p},
                             float(per.get(p, 0)))
        for key in list(known):
            shard = dict(key).get("shard")
            try:
                shard_i = int(shard)
            except (TypeError, ValueError):
                continue
            if shard_i >= self.mesh_size and shard_i not in per:
                st.gauge_labeled("tpu_shard_hbm_bytes",
                                 {"shard": shard_i}, 0.0)

    @staticmethod
    def _served_epoch(dev) -> int:
        """The store epoch a snapshot actually serves: the base pin
        epoch, advanced by every applied delta commit group."""
        return (dev.delta.applied_epoch if dev.delta is not None
                else dev.epoch)

    @staticmethod
    def _delta_flag() -> int:
        """`tpu_delta_max_edges`: negative (the default) = the delta
        plane is armed wherever the store feeds one, its per-(block,
        part) capacity worked out at pin time (`_delta_capacity`); a
        positive value fixes that capacity; 0 = delta plane off (every
        epoch bump re-pins; byte-identical to the pre-delta runtime)."""
        try:
            return int(get_config().get("tpu_delta_max_edges"))
        except Exception:  # noqa: BLE001 — config missing in odd embeds
            return 0

    # -- the delta plane's capacity, from what a pin already sees -----
    # A (block, part) delta buffer holds 1/DELTA_EDGE_SHARE of the
    # part's padded edge slots (a plane past a few percent of its base
    # is a compaction's to fold in, and the merge's sorts and re-puts
    # grow with it), rounded up to a power of two (the buffers' width is
    # in every program's key).  Never under DELTA_MIN_EDGES: a serving
    # window's writes (some hundreds at the INSERT path's rate, all in
    # one part at worst) must stay under the compaction watermark
    # (0.75) whatever the graph's size.  And all delta buffers of a
    # device together take at most 1/DELTA_HBM_SHARE of the HBM headroom
    # `_check_hbm_budget` finds under `tpu_hbm_limit_bytes`, halving until
    # they do: the plane never costs a graph its pin.
    # Past the watermark a compaction folds the plane's host mirror
    # into a fresh base (`_compact`: no export; base rows minus
    # tombstones plus delta rows, dense ids and epoch kept) and swaps
    # it in under the gate's write side.  The writes that land while it
    # builds keep going to the OLD plane, whose last quarter (capacity x
    # (1 - watermark) slots a (block, part)) absorbs them; the swap
    # carries them to the new plane (`HostDelta.adopt`).  The base is
    # pinned with at least one capacity of free edge slots a part
    # (`pad_edge_width`), so a fold keeps every padded width and the
    # programs compiled over the old base serve the new one.
    DELTA_EDGE_SHARE = 64
    DELTA_MIN_EDGES = 1 << 10
    DELTA_HBM_SHARE = 64
    # a failed compaction is tried again after this many seconds,
    # doubling with every failure in a row up to the maximum
    COMPACT_BACKOFF_S = (1.0, 60.0)
    # re-runs of one statement on the snapshot that replaced its own
    RETIRED_RETRIES = 3

    def _delta_capacity(self, snap, headroom: Optional[int]) -> int:
        """Per-(block, part) delta capacity in edges for `snap` (the
        rule above), `headroom` the free HBM bytes a device has under
        its limit once the snapshot is pinned (None = no limit set)."""
        width = max((b.nbr.shape[1] for b in snap.blocks.values()),
                    default=0)
        cap = max(_delta_pow2(-(-width // self.DELTA_EDGE_SHARE)),
                  self.DELTA_MIN_EDGES)
        if headroom is not None:
            # the buffers' bytes are linear in the capacity: one slot of
            # every block, over the parts one device holds
            slot = HostDelta(snap, 1).nbytes()
            if not self.local_mode and snap.num_parts == self.mesh_size:
                slot = -(-slot // snap.num_parts)
            while cap > 1 and \
                    cap * slot > headroom // self.DELTA_HBM_SHARE:
                cap //= 2
        return cap

    @staticmethod
    def _delta_slack() -> int:
        try:
            return max(int(get_config().get("tpu_delta_vmax_slack")), 0)
        except Exception:  # noqa: BLE001
            return 0

    def pin(self, store: GraphStore, space: str,
            force: bool = False) -> DeviceSnapshot:
        sd = store.space(space)
        for _ in range(1 + self.RETIRED_RETRIES):
            cur = self.snapshots.get(space)
            # uid guards the (space-name, epoch) cache against a
            # DIFFERENT store object whose same-named space happens to
            # share the epoch value (one shared runtime + two stores
            # served the wrong graph); accessors without a uid (cluster
            # _SpaceView, bench shims) keep the plain epoch check
            if cur is None or force or getattr(
                    cur, "space_uid", None) != getattr(sd, "uid", None):
                break
            # the freshness probe every dispatch pays: on a cluster
            # store `sd.epoch` is one `storage.probe` RPC per storaged host
            with _t.span("tpu:snapshot_check", space=space):
                fresh = self._served_epoch(cur) == sd.epoch
            if fresh:
                return cur
            if cur.delta is None or not hasattr(store, "delta_records"):
                break
            # ISSUE 19 fast path: fold the dirty-key log into the
            # resident delta plane (one small put per commit group)
            # instead of a graph-sized rebuild + re-pin
            dev = self._try_delta_update(store, space, cur)
            if dev is not None:
                return dev
            if self.snapshots.get(space) is cur:
                break                   # the plane cannot take it: rebuild
            # a compaction's swap (or another pin) replaced `cur` while
            # the apply waited for the gate: the snapshot that serves
            # now takes the delta, not a whole export
        dflag = self._delta_flag()
        snap = self._build_fresh(store, space, dflag)
        headroom = self._check_hbm_budget(snap, space)
        cap = self._plane_capacity(store, snap, dflag, headroom)
        if cap:
            # the free edge slots a compaction folds the plane into
            pad_edge_width(snap, cap)
        # the device_put runs under the WRITE side of the dispatch
        # gate: in-flight dispatches drain first, new ones wait — the
        # jaxlib serve-while-repin race window is closed, and the
        # exclusive wait itself is telemetry (how long an epoch bump
        # waited on the serving plane)
        from ..utils.stats import stats
        wait_s = self._gate.acquire_write()
        try:
            # donate the replaced epoch's buffers BEFORE the new put so
            # peak HBM through a re-pin stays ~1x the snapshot, not 2x;
            # no dispatch can hold them (readers drained), and any
            # thread still carrying the old DeviceSnapshot object sees
            # `retired` under its next read gate and re-pins
            old = self.snapshots.get(space)
            if old is not None and not force and not old.retired \
                    and self._served_epoch(old) == sd.epoch \
                    and getattr(old, "space_uid", None) == getattr(
                        sd, "uid", None):
                # a concurrent first-touch pin of the same space won the
                # gate first — adopt its snapshot instead of retiring it
                # (retiring here would fail that thread's dispatch)
                return old
            if old is not None:
                old.delete_buffers()
            dev = pin_snapshot(snap, self.mesh)
            dev.space_uid = getattr(sd, "uid", None)
            self.snapshots[space] = dev
            # stale-epoch jitted fns are keyed by epoch; drop them
            self._fns = {k: v for k, v in self._fns.items()
                         if not (k[0] == space and k[1] != dev.epoch)}
            if cap:
                # an EMPTY plane, allocated at pin time (gate held):
                # lazy allocation would change kernel input shapes on
                # the first write and recompile every cached program;
                # an empty plane costs one small put, compiles once, and
                # costs a read nothing until it holds something (hop.py
                # `_delta_live`)
                put_delta_blocks(dev, HostDelta(snap, cap))
        finally:
            self._gate.release_write()
        stats().observe("tpu_repin_wait_us", int(wait_s * 1e6))
        stats().inc("tpu_pins")
        self._emit_hbm_gauges()
        self._emit_delta_gauges(dev)
        return dev

    def _build_fresh(self, store, space: str, dflag: int):
        """Build a CsrSnapshot for a full (re)pin.  When the delta plane
        is on, the store starts (or keeps) watching dirty keys BEFORE
        the export — a key noted between watch and export is merely
        re-read at apply time, so there is no lost-write window."""
        if dflag != 0 and hasattr(store, "delta_watch"):
            store.delta_watch(space)
        if hasattr(store, "build_csr_snapshot"):
            # cluster store: bulk per-part CSR export over RPC (the
            # north-star storage addition) instead of a local walk
            try:
                snap = store.build_csr_snapshot(space)
            except Exception as ex:  # noqa: BLE001 — RPC/meta errors
                # surface as device-unavailable so executors fall back
                # to the host path instead of failing the query; the
                # ORIGINAL cause is counted and logged here, because
                # the executor site only sees the TpuUnavailable
                note_host_fallback("csr_export", ex)
                raise TpuUnavailable(
                    f"cluster CSR export failed: {ex}") from ex
        else:
            snap = build_snapshot(
                store, space,
                vmax_extra=self._delta_slack() if dflag != 0 else 0)
        return self._maybe_degree_split(snap)

    def _plane_capacity(self, store, snap, dflag: int,
                        headroom: Optional[int]) -> int:
        """The delta plane's capacity in edges a (block, part) for a
        pin of `snap` from `store`, 0 where the pin arms none: the flag
        is an explicit 0, the store feeds no plane (`delta_records`,
        `delta_reader`), or the snapshot is degree-split (hub rows
        re-home edges, so delta row identity breaks).  `dflag` > 0
        fixes it; < 0 takes `_delta_capacity` within `headroom`, halved
        on until the free edge slots the base is pinned with beside it
        (`pad_edge_width`: one capacity a part of every block) also fit
        1/DELTA_HBM_SHARE of that headroom: neither the plane nor the
        slack costs a graph its pin."""
        if dflag == 0 or getattr(snap, "hub_dense", None) is not None \
                or not (hasattr(store, "delta_records")
                        and hasattr(store, "delta_reader")):
            return 0
        if dflag > 0:
            return dflag
        cap = self._delta_capacity(snap, headroom)
        if headroom is not None:
            slot = sum(b.nbr.shape[0] * (
                b.nbr.itemsize + b.rank.itemsize
                + sum(c.itemsize for c in b.props.values()))
                for b in snap.blocks.values())
            if not self.local_mode and snap.num_parts == self.mesh_size:
                slot = -(-slot // snap.num_parts)
            while cap > 1 and \
                    cap * slot > headroom // self.DELTA_HBM_SHARE:
                cap //= 2
        return cap

    def _try_delta_update(self, store, space: str, cur):
        """Advance a delta-armed snapshot to the store's epoch without
        re-pinning.  Returns the snapshot on success, None to signal
        the full-rebuild path (log broken/overflow/unsupported key —
        the rebuild discards every partially-mutated mirror).

        One `tpu:delta_apply` span (phase `delta_apply`) with a child
        for each thing a fresh read waits for: the store's census
        (`tpu:delta_census`, twice: before and under the gate), the
        gate (`tpu:delta_gate`), the re-read of every dirty key
        (`tpu:delta_reread`) and the put of the changed blocks
        (`device:delta_put`).  `tpu_delta_apply_s` is entry to return,
        whatever the outcome."""
        t0 = time.perf_counter()
        try:
            with _t.span("tpu:delta_apply", space=space):
                return self._delta_update(store, space, cur)
        finally:
            _metrics().add_value("tpu_delta_apply_s",
                                 time.perf_counter() - t0)

    def _delta_update(self, store, space: str, cur):
        def census():
            with _t.span("tpu:delta_census"):
                return store.delta_records(space)

        rec = census()
        if rec is None:
            return None
        _, _, floor = rec
        if floor > cur.delta.applied_epoch:
            return None                 # log gap: keys before floor lost
        with _t.span("tpu:delta_gate"):
            wait_s = self._gate.acquire_write()
        try:
            dev = self.snapshots.get(space)
            if dev is not cur or dev.retired or dev.delta is None:
                return None
            # re-read under the gate: writers that landed while we
            # waited are folded into this same apply
            rec = census()
            if rec is None:
                return None
            keys, target, floor = rec
            if floor > dev.delta.applied_epoch:
                return None
            if target == dev.delta.applied_epoch:
                return dev               # a concurrent update got there
            try:
                with _t.span("tpu:delta_reread", keys=len(keys)):
                    changes = dev.delta.host.apply(
                        store.delta_reader(space), keys)
            except (DeltaOverflow, DeltaUnsupported):
                return None
            t_put = time.perf_counter()
            with _t.span("device:delta_put", blocks=len(changes.blocks)):
                put_delta_blocks(dev, dev.delta.host,
                                 sorted(changes.blocks))
                host = dev.delta.host.snap
                putter = None
                if changes.num_vertices:
                    from .device import make_putter
                    putter = make_putter(dev.mesh, dev.num_parts)
                    dev.num_vertices = putter(
                        np.asarray(host.num_vertices, np.int32))
                if changes.tag_cols:
                    from .device import make_putter
                    putter = putter or make_putter(dev.mesh,
                                                   dev.num_parts)
                    for tag, colname in sorted(changes.tag_cols):
                        dt = dev.tags.get(tag)
                        tt = host.tags.get(tag)
                        if dt is None or tt is None:
                            continue
                        if colname == "present":
                            dt.present = putter(tt.present)
                        else:
                            dt.props[colname] = putter(tt.props[colname])
            _metrics().add_value("tpu_delta_put_s",
                                 time.perf_counter() - t_put)
            dev.delta.applied_epoch = target
            store.delta_trim(space, keys)
            carry = getattr(dev, "_compact_carry", None)
            if carry is not None:
                # a compaction is folding a copy of the mirror taken
                # before this apply: its swap carries these keys over
                carry.update(keys)
        finally:
            self._gate.release_write()
        st = _metrics()
        # the apply's own wait for the gate: `tpu_repin_wait_us` is a
        # re-pin's, which this avoided
        st.observe("tpu_delta_gate_wait_us", int(wait_s * 1e6))
        st.add_value("tpu_delta_keys", len(keys))
        st.inc("tpu_repin_avoided")
        self._emit_delta_gauges(dev)
        self._maybe_compact(store, space, dev)
        return dev

    @staticmethod
    def _delta_sig(dev):
        """STATIC delta shape identity for jit cache keys: caps only —
        putting the delta epoch here would recompile every program on
        every commit group and erase the perf win.  Compiled programs
        stay valid across applies because only array CONTENT changes
        (blocks_data is rebuilt per dispatch)."""
        if dev.delta is None:
            return None
        hd = dev.delta.host
        return ("delta", hd.dcap, hd.tcap)

    @staticmethod
    def _grab_delta(dev, block_keys, prop_names):
        """Grab ONE mutually-consistent delta view for a dispatch:
        (view, per-block kernel-leaf dicts).  `view` is the atomic
        (epoch, blocks) tuple — the materializers must decode this
        dispatch's capture against view[1]'s numpy mirrors, never
        against dev.delta's CURRENT state (an apply may land between
        launch and materialize; it replaces, never mutates, so the
        grabbed arrays stay coherent)."""
        if dev.delta is None:
            return None, [None] * len(block_keys)
        view = dev.delta.view
        extras = []
        for bk in block_keys:
            e = view[1].get(bk)
            if e is None:
                extras.append(None)
                continue
            d = {k: e[k] for k in ("d_src", "d_dst", "d_rank",
                                   "d_valid", "d_tomb")}
            d["d_props"] = {n: e["d_props"][n] for n in prop_names}
            extras.append(d)
        return view, extras

    def _emit_delta_gauges(self, dev) -> None:
        from ..utils.stats import stats
        if dev.delta is None:
            return
        hd = dev.delta.host
        st = stats()
        st.gauge("tpu_delta_edges",
                 float(hd.total_edges() + hd.total_tombs()))
        st.gauge("tpu_delta_bytes", float(hd.nbytes()))
        # per (block, part): fill is the fullest buffer's rows or
        # tombstones over it
        st.gauge("tpu_delta_capacity_edges", float(hd.dcap))
        st.gauge("tpu_delta_fill_ratio", float(hd.fill_ratio()))
        per = hd.edges_per_part()
        tpp = hd.tombs_per_part()
        for p in range(dev.num_parts):
            st.gauge_labeled("tpu_shard_delta_edges", {"shard": p},
                             float(per[p] + tpp[p]))

    def _maybe_compact(self, store, space: str, dev) -> None:
        """Watermark check after a delta apply: past the fill threshold,
        kick the background compaction (fold the plane into a new base
        off the gate, swap under a short exclusive hold).  One at a
        time, and after a failure not before its back-off has run."""
        try:
            wm = float(get_config().get("tpu_delta_compact_watermark"))
        except Exception:  # noqa: BLE001
            wm = 0.0
        if wm <= 0 or dev.delta is None or dev.retired:
            return
        if dev.delta.host.fill_ratio() < wm:
            return
        if getattr(dev, "_compacting", False) or \
                time.monotonic() < getattr(dev, "_compact_not_before", 0.0):
            return
        dev._compacting = True
        t = threading.Thread(target=self._compact,
                             args=(store, space, dev), daemon=True,
                             name=f"tpu-compact-{space}")
        dev._compact_thread = t
        t.start()

    def _compact(self, store, space: str, dev) -> None:
        """Fold the delta back into a fresh base CSR, asking the store
        for nothing.  Three steps, a span and a series each:

        `tpu:compact_build`, OFF the gate: a copy of the plane's host
        mirror (taken between two applies, under the mirror's own lock:
        a compaction does not queue at the gate to read) is folded into
        a new base, base rows minus tombstones plus delta rows in
        canonical CSR order (`fold_base`).  Dense ids, the vid dictionary, the string
        pool, the vertex tables and the epoch are the old base's, so
        seeds resolve the same before and after and the compiled
        programs stay valid; the padded widths stay while the rows fit
        (`pad_edge_width` left a capacity of free slots).  Reads and
        applies keep flowing against the old base and plane meanwhile;
        every key applied from the copy on is noted
        (`dev._compact_carry`).

        `tpu:compact_gate`: the wait for the gate's write side.

        `tpu:compact_swap`, the hold: the noted keys are carried into
        the new plane from the old mirror (`HostDelta.adopt`: what base
        plus plane held for each, no store read), the old buffers are
        given up, the new base pinned and its plane armed at the old
        plane's capacity and `applied_epoch`.  New base plus new plane
        is old base plus old plane, edge for edge: no write applied
        before, during or after the build is lost, and the log, which
        was never re-watched, still holds what is not applied yet.  A
        statement that holds the old snapshot meets `SnapshotRetired`
        at the gate and runs again on the new one.

        A compaction that raises is counted by cause
        (`tpu_compaction_failures`), logged once, and backs off; the
        old snapshot keeps serving."""
        from ..utils.failpoints import FailpointError, fail
        st = _metrics()
        new = None
        try:
            with _t.start_trace("tpu:compaction", service="graphd",
                                space=space):
                t0 = time.perf_counter()
                with _t.span("tpu:compact_build"):
                    old_hd = dev.delta.host
                    # first the note, then the copy: an apply that the
                    # copy misses finds the note and leaves its keys
                    dev._compact_carry = {}
                    ins, tomb = old_hd.freeze()
                    snap = fold_base(old_hd.snap, ins, tomb, old_hd.dcap)
                    # the new base is a few rows larger than the one it
                    # replaces: it is held to the same budget, and a
                    # refusal leaves the old one serving
                    self._check_hbm_budget(snap, space)
                st.add_value("tpu_compact_build_s",
                             time.perf_counter() - t0)
                fail.hit("tpu:compact_swap", key=space)
                with _t.span("tpu:compact_gate"):
                    self._gate.acquire_write()
                t1 = time.perf_counter()
                try:
                    with _t.span("tpu:compact_swap"):
                        if self.snapshots.get(space) is not dev \
                                or dev.retired:
                            return       # superseded while building
                        carried = dev._compact_carry
                        new_hd = HostDelta(snap, old_hd.dcap, old_hd.tcap)
                        new_hd.adopt(old_hd, carried,
                                     store.delta_reader(space).dense_of)
                        dev.delete_buffers()
                        new = pin_snapshot(snap, self.mesh)
                        new.space_uid = dev.space_uid
                        # the plane covers what the old one covered, and
                        # its device epoch runs on: a lane assembled over
                        # the old plane never joins one of the new
                        new.delta = DeviceDelta(
                            host=new_hd,
                            applied_epoch=dev.delta.applied_epoch,
                            epoch=dev.delta.epoch)
                        put_delta_blocks(new, new_hd)
                        self.snapshots[space] = new
                        st.inc("tpu_compact_carried_keys", len(carried))
                finally:
                    self._gate.release_write()
                    if new is not None:
                        st.add_value("tpu_compact_swap_s",
                                     time.perf_counter() - t1)
                st.inc("tpu_compactions")
                self._emit_delta_gauges(new)
                self._emit_hbm_gauges()
        except FailpointError:
            pass                         # KILL test hook: abort cleanly
        except Exception as ex:  # noqa: BLE001 — the thread ends here
            fails = dev._compact_fails = \
                getattr(dev, "_compact_fails", 0) + 1
            first, most = self.COMPACT_BACKOFF_S
            wait = min(first * 2 ** (fails - 1), most)
            dev._compact_not_before = time.monotonic() + wait
            st.inc("tpu_compaction_failures")
            st.inc_labeled("tpu_compaction_failures_by_cause",
                           {"cause": type(ex).__name__})
            _log.warning(
                "compaction of %s failed (%s: %s); the old base keeps "
                "serving, next attempt in %.0f s at the earliest",
                space, type(ex).__name__, ex, wait)
        finally:
            dev._compact_carry = None
            dev._compacting = False

    def _check_hbm_budget(self, snap, space: str) -> Optional[int]:
        """HBM budget (SURVEY §2 row 5: device memory is the scarce
        resource): refuse to pin past the PER-DEVICE limit; the caller
        falls back to the host path instead of OOMing the chip.
        Returns the bytes a device keeps free under the limit once
        `snap` is pinned (what `_delta_capacity` sizes the delta plane
        within); None when no limit is set.

        The limit is per device — that is the scale-out contract: a
        snapshot sharded P ways parks hbm_bytes/P on each chip, so an
        8-way mesh accepts a graph 8× the single-chip budget (ROADMAP
        item 1's "fills a pod, not a chip")."""
        from ..utils.memtracker import get_config as _gc  # flag defined there
        limit = int(_gc().get("tpu_hbm_limit_bytes"))
        if not limit:
            return None
        P = self.mesh_size if (not self.local_mode
                               and snap.num_parts == self.mesh_size) else 1
        est = -(-snap.hbm_bytes() // P)
        others = 0
        for sp_, s in self.snapshots.items():
            if sp_ == space:
                continue
            others += max(s.shard_hbm_bytes().values(), default=0)
        if est + others > limit:
            raise TpuUnavailable(
                f"snapshot needs {est:,}B HBM per device "
                f"({P} shard(s)); {others:,}B already pinned per device, "
                f"limit {limit:,} (flag tpu_hbm_limit_bytes)")
        return limit - est - others

    @staticmethod
    def _maybe_degree_split(snap):
        """Apply the supernode degree-split at pin time when the flag
        is set (SURVEY §7 hard-part #4): the pinned copy AND its host
        mirror share the split layout, so eidx decode is unchanged."""
        try:
            thr = int(get_config().get("tpu_degree_split_threshold"))
        except Exception:  # noqa: BLE001 — config missing in odd embeds
            thr = 0
        if thr > 0 and getattr(snap, "hub_dense", None) is None:
            from ..graphstore.csr import degree_split
            snap = degree_split(snap, thr)
        return snap

    def pin_prebuilt(self, snap) -> DeviceSnapshot:
        """Pin an externally-built CsrSnapshot (bulk-ingest / bench path
        — no dict store behind it)."""
        snap = self._maybe_degree_split(snap)
        self._check_hbm_budget(snap, snap.space)
        wait_s = self._gate.acquire_write()
        try:
            old = self.snapshots.get(snap.space)
            if old is not None:
                old.delete_buffers()
            dev = pin_snapshot(snap, self.mesh)
            self.snapshots[snap.space] = dev
        finally:
            self._gate.release_write()
        from ..utils.stats import stats
        stats().observe("tpu_repin_wait_us", int(wait_s * 1e6))
        stats().inc("tpu_pins")
        self._emit_hbm_gauges()
        self._emit_delta_gauges(dev)
        return dev

    def unpin(self, space: str):
        self._gate.acquire_write()
        try:
            old = self.snapshots.pop(space, None)
            if old is not None:
                old.delete_buffers()
            self._fns = {k: v for k, v in self._fns.items()
                         if k[0] != space}
            self._kmax = {k: v for k, v in self._kmax.items()
                          if k[0] != space}
            self._fetch_warm = {w for w in self._fetch_warm
                                if w[0][0] != space}
            self._buckets = {k: v for k, v in self._buckets.items()
                             if k[0][0] != space}
        finally:
            self._gate.release_write()
        self._emit_hbm_gauges()

    def hbm_bytes(self) -> int:
        return sum(s.hbm_bytes() for s in self.snapshots.values())

    def _save_buckets(self):
        if not self._buckets_path:
            return
        try:
            import ast as _ast
            import json as _json
            import os as _os
            # MERGE with the on-disk contents: several runtimes (one per
            # engine) share the cache file, and a plain overwrite made
            # the last saver clobber every other program's converged
            # buckets (each process then re-climbed the recompile ladder)
            merged = {}
            try:
                with open(self._buckets_path) as f:
                    merged = {_ast.literal_eval(k): tuple(v)
                              for k, v in _json.load(f).items()}
            except Exception:  # noqa: BLE001 — absent/corrupt file
                merged = {}
            merged.update(self._buckets)
            tmp = self._buckets_path + ".tmp"
            with open(tmp, "w") as f:
                _json.dump({repr(k): list(v)
                            for k, v in merged.items()}, f)
            _os.replace(tmp, self._buckets_path)
        except Exception:  # noqa: BLE001 — cache is best-effort
            pass

    # -- traversal --------------------------------------------------------

    def _seed_builder(self, target, P: int, vmax: int, lanes: bool):
        """The jitted seed-bitmap scatter builder, cached and bounded —
        ONE copy of the build closure, sharding resolution and eviction
        policy for the solo and lane-batched preps.  `lanes` vmaps the
        same build over a leading lane axis ((L, cap) ids →
        (L, P, vmax) bitmap stack).  Returns (cache key, fn)."""
        key = ("seedfr_lanes" if lanes else "seedfr", target, P, vmax)
        fn = self._seed_fns.get(key)
        if fn is not None:
            return key, fn
        if not isinstance(target, jax.sharding.Sharding):
            sh = jax.sharding.SingleDeviceSharding(target)
        else:
            sh = target

        def build(dpad):
            valid = dpad >= 0
            rows = jnp.where(valid, dpad % P, 0)
            cols = jnp.where(valid, dpad // P, 0)
            fr = jnp.zeros((P, vmax), bool)
            return fr.at[rows, cols].max(valid)

        fn = jax.jit(jax.vmap(build) if lanes else build,
                     out_shardings=sh)
        self._seed_fns[key] = fn
        # bounded: the key embeds the sharding target and snapshot
        # vmax, so a long-lived server re-pinning growing snapshots
        # must not accumulate executables for the process lifetime
        while len(self._seed_fns) > 32:
            old = next(iter(self._seed_fns))
            self._seed_fns.pop(old)
            self._seed_warm = {w for w in self._seed_warm
                               if w[0] != old}
        return key, fn

    def _seed_frontier_prep(self, dev: DeviceSnapshot,
                            lane_dense: Sequence[Sequence[int]],
                            lanes: bool):
        """Prep for the on-device seed-bitmap build: pad each launch
        lane's dense-id list to one pow2 bucket and return (pad, jitted
        builder) with the builder already COMPILED for this shape —
        first-bucket XLA trace/compile must not be charged to put_s (it
        would report a one-off compile as steady-state transfer cost).

        The builder scatter-ors the ids into a (P, vmax) bool bitmap on
        device (dense = local * P + p), so the per-query host→device
        transfer shrinks from the graph-sized zeros bitmap (8 MB at
        north-star scale) to the seed ids.  A solo launch pads its one
        list to (cap,).  A lane-shaped launch pads to one (L, cap) block
        that the vmapped scatter builds into a (L, P, vmax) frontier
        stack: L is pow2-padded so the compile count stays logarithmic
        in batch size, and padding lanes (all -1) scatter nothing and
        expand nothing."""
        P, vmax = dev.num_parts, dev.vmax
        ds = [sorted({int(x) for x in d if x >= 0}) for d in lane_dense]
        top = max((d[-1] for d in ds if d), default=-1)
        if top >= P * vmax:
            # an id from a stale/foreign snapshot: the old host-side
            # numpy build crashed loudly, a JAX scatter would DROP it
            raise ValueError(
                f"dense seed id {top} out of range for snapshot "
                f"(P={P}, vmax={vmax})")
        shape: Tuple[int, ...] = (_pow2(max([len(d) for d in ds] + [1])),)
        # lanes × shards grid: the frontier stack is sharded over BOTH
        # mesh axes — each device owns its lane rows of its partition's
        # bitmap.  On a legacy 1-D ('part',) mesh the lane dimension
        # stays unsharded (replicated lanes).
        spec = PartitionSpec("part")
        if lanes:
            # on a (lanes, parts) mesh the global lane axis must divide
            # evenly over the lane-axis rows: pad to Lm × pow2 lanes
            # (Lm=1 in local mode reduces to the plain pow2 bucket)
            Lm = max(self.mesh_lanes, 1)
            shape = (Lm * _pow2(max(-(-len(ds) // Lm), 1)),) + shape
            spec = PartitionSpec(
                "lane" if "lane" in self.mesh.axis_names else None, "part")
        pad = np.full(shape, -1, np.int64)
        for row, d in zip(pad if lanes else pad[None], ds):
            row[:len(d)] = d
        target = (self.mesh.devices.reshape(-1)[0] if self.local_mode
                  else NamedSharding(self.mesh, spec))
        key, fn = self._seed_builder(target, P, vmax, lanes)
        wk = (key,) + shape
        if wk not in self._seed_warm:
            with self._collective_launch():
                jax.block_until_ready(fn(pad))   # compile outside timer
            self._seed_warm.add(wk)
        return pad, fn

    def _escalate(self, dev: DeviceSnapshot, dense: Sequence[int],
                  key_fn, build_fn, inputs_fn, stats: "TraverseStats",
                  n_hops: int = 1, uniform: bool = False,
                  fetch_keys: Optional[set] = None,
                  kernel: str = "traverse",
                  eb_cap: Optional[int] = None):
        """A solo statement's launch: the one whose single lane is this
        statement's seeds and whose program is the solo program, under
        the dispatch gate (`_gated_dispatch`, ISSUE 9), charged to the
        statement on its own thread."""
        with self._gated_dispatch(kernel) as wait_us:
            res, info = self._escalate_locked(
                dev, [dense], key_fn, build_fn, inputs_fn, wait_us,
                n_hops=n_hops, uniform=uniform, fetch_keys=fetch_keys,
                kernel=kernel, eb_cap=eb_cap)
            self._attribute(info, res, None, stats)
            return res

    @contextmanager
    def _gated_dispatch(self, kernel: str):
        """The dispatch-gate prologue/epilogue shared by EVERY device
        program (the escalation driver and the algo plane's
        single-shot iterations): register in the live DispatchTable
        (queued → running → done), hit the `tpu:dispatch_gate`
        failpoint, wait on the READ side of the dispatch-vs-repin
        gate, and land the wait in `tpu_dispatch_queue_us{kernel}`,
        the statement's cost sink and its live-registry row.  Yields
        the queue wait in µs.  Defined ONCE so a change to dispatch
        accounting cannot drift between the two paths."""
        from ..utils.failpoints import fail as _fail
        from ..utils.stats import current_cost
        from ..utils.workload import current_live, dispatch_table
        tok = dispatch_table().enter(kernel)
        acquired = False
        try:
            # inside the try: a `raise` action must still exit the
            # token, or GET /queries shows a phantom forever-queued
            # dispatch and the depth gauge sticks at 1
            with _t.span("device:queue", kernel=kernel):
                _fail.hit("tpu:dispatch_gate", key=kernel)
                self._gate.acquire_read()
                acquired = True
                wait_us = dispatch_table().mark_running(tok)
            _metrics().observe("tpu_dispatch_queue_us", wait_us,
                               {"kernel": kernel})
            cc = current_cost()
            if cc is not None:
                cc.add("queue_us", wait_us)
            lv = current_live()
            if lv is not None:
                lv.add("queue_us", wait_us)
            yield wait_us
        finally:
            if acquired:
                self._gate.release_read()
            dispatch_table().exit(tok)

    @contextmanager
    def _collective_launch(self):
        """Serialize device programs that contain mesh collectives.
        On a multi-part mesh every launch (kernel run, seed warm-up,
        seed put) holds the mutex for the duration of the execution:
        concurrent collective programs on overlapping devices
        interleave their all_to_all rendezvous and deadlock.  A no-op
        in local mode — the vmapped single-chip programs have no
        collectives and dispatch concurrently as before.  What a launch
        waited for the mutex is one observation of
        `tpu_collective_wait_s` (emitted after the release: nothing is
        added to the extent the mutex covers) and, inside a statement's
        trace, a `device:launch_wait` span."""
        if self.local_mode:
            yield
            return
        t0 = time.perf_counter()
        with _t.span("device:launch_wait"):
            self._launch_mutex.acquire()
        wait_s = time.perf_counter() - t0
        try:
            yield
        finally:
            self._launch_mutex.release()
            _metrics().add_value("tpu_collective_wait_s", wait_s)

    def algo_dispatch(self, kernel: str, fn, *args):
        """One gated single-shot device dispatch for the algo plane
        (ISSUE 13): a vertex-program ITERATION kernel has static
        full-graph shapes — no bucket escalation, no capture fetch —
        but it rides the same gate/accounting as every other device
        program (_gated_dispatch) and additionally lands its run time
        in `tpu_dispatch_us{kernel}`, `device_us` and the SHOW QUERIES
        decomposition.  Returns (result, dispatch_us)."""
        from ..utils.stats import current_cost, current_work
        from ..utils.workload import current_live
        with self._gated_dispatch(kernel):
            t0 = time.perf_counter()
            with self._collective_launch():
                res = fn(*args)
                jax.block_until_ready(res)
            us = int((time.perf_counter() - t0) * 1e6)
            _metrics().observe("tpu_dispatch_us", us, {"kernel": kernel})
            cc = current_cost()
            if cc is not None:
                cc.add("device_us", us)
                cc.add("device_dispatches", 1)
            lv = current_live()
            if lv is not None:
                lv.add("device_us", us)
                lv.add("dispatches", 1)
            wc = current_work()
            if wc is not None:
                wc.add("device_dispatches")
            return res, us

    # -- the escalation driver: solo and lane-batched launches -----------

    def _try_batched(self, dense: Sequence[int], dev: DeviceSnapshot,
                     key_fn, build_fn, inputs_fn, n_hops: int,
                     uniform: bool, fetch_keys: Optional[set],
                     kernel: str, stats: "TraverseStats",
                     delta_epoch: Optional[int] = None):
        """Submit this dispatch to the batch former (ISSUE 15); returns
        the statement's solo-shaped {"cap": ...} after a shared launch,
        or None when the dispatch should run solo (batching off, no
        concurrent company, a mesh the snapshot is not sharded for, or
        the `tpu:batch_form` failpoint rejected enrollment).
        `build_fn(ebs)` builds the LANES program.

        Sharded meshes batch too (PR 17): the lanes program is the
        lanes × shards shard_map when local_mode is off, and the
        compatibility key carries the mesh shape + epoch so a re-pin to
        a different shard count can never merge lanes compiled for
        different launch grids."""
        if not self.local_mode and dev.num_parts != self.mesh_size:
            return None
        from ..utils.failpoints import FailpointError
        from .batch import batch_former
        former = batch_former()
        if not former.enabled():
            return None
        # the delta device epoch the CALLER assembled against rides the
        # compatibility key (NOT the jit key): statements grouped into
        # one launch must share the exact same delta buffers, or a lane
        # could read another statement's pre-write view (read-your-
        # writes floor, PR 9)
        base_key = (kernel, key_fn(()),
                    frozenset(fetch_keys) if fetch_keys is not None
                    else None, ("mesh",) + self._mesh_key(),
                    ("delta", delta_epoch)
                    if delta_epoch is not None else None)

        def launch(lane_dense):
            # ONE gated dispatch, ONE put, ONE fetch for every lane of
            # the formed batch, on the launcher member's thread; it
            # consumes ONE `tpu_dispatch_queue_cap` slot, never K.
            # Per-statement TLS attribution (work/cost/live/trace) is
            # SUPPRESSED while it runs: each member charges its own
            # lane on its own thread (_attribute), so rows,
            # WorkCounters, cost sinks and flight entries stay exactly
            # per-statement (the PR 7 concurrent-attribution contract)
            from ..utils.stats import use_cost, use_work
            from ..utils.workload import use_live
            ctx = _t.current_ctx()
            with use_work(None), use_cost(None), use_live(None), \
                    _t.use_ctx(None), \
                    self._gated_dispatch(kernel) as wait_us:
                return self._escalate_locked(
                    dev, lane_dense, key_fn, build_fn, inputs_fn,
                    wait_us, n_hops=n_hops, uniform=uniform,
                    fetch_keys=fetch_keys, kernel=kernel, lanes=True,
                    launcher_ctx=ctx)

        try:
            tk = former.submit(base_key, dense, launch, kernel=kernel,
                               gate_busy=self._gate.write_held)
        except FailpointError:
            return None          # batch forming rejected → solo dispatch
        if tk is None:
            return None
        self._attribute(tk.info, tk.res, tk.lane, stats, tk.form_wait_us)
        return {"cap": {k: v[tk.lane] for k, v in tk.res["cap"].items()}}

    @staticmethod
    @contextmanager
    def _phase(phases: list, name: str, **attrs):
        """One device phase of a launch: a span LIVE where a trace is
        active (a solo statement's: the benchmark's trace reduction
        labels idle gaps by the spans open at a gap's midpoint and the
        phase ledger folds them) and a (name, perf_counter start,
        seconds, attrs) record in the launch's phase list, which a
        shared launch's members replay into their own traces
        (_attribute): nothing is traced on the launcher's thread while
        the launch runs."""
        t0 = time.perf_counter()
        with _t.span(name, **attrs):
            yield
        phases.append((name, t0, time.perf_counter() - t0, attrs))

    def _escalate_locked(self, dev: DeviceSnapshot,
                         lane_dense: Sequence[Sequence[int]],
                         key_fn, build_fn, inputs_fn, wait_us: int,
                         n_hops: int = 1, uniform: bool = False,
                         fetch_keys: Optional[set] = None,
                         kernel: str = "traverse", lanes: bool = False,
                         launcher_ctx=None, eb_cap: Optional[int] = None):
        """The power-of-two bucket escalation driver of every device
        program (traverse, hops, bfs), solo or lane-batched: seed
        bitmap put, jit cache, overflow-driven retry (SURVEY §7
        hard-part #1), fetch, launch-level accounting.  Runs inside the
        caller's `_gated_dispatch`, whose wait is `wait_us`.

        A launch is a list of lanes, each a statement's dense seed ids.
        A solo statement is the launch of one lane that runs the solo
        program; with `lanes` the program carries a leading lane axis
        and every result leaf is lane-major (hop_edges (L, P, steps),
        cap arrays with a leading L).

        key_fn(ebs) → jit-cache key; build_fn(ebs) → jitted program
        fn(*inputs, frontier); inputs_fn(ebs) → tuple of extra inputs;
        ebs is the per-hop edge-budget tuple (len n_hops).

        With the bitmap frontier (round-4 redesign) the only dynamic
        budget is the per-block edge budget — the frontier and the
        routing buckets are structurally overflow-free.  Budgets are
        per-hop: hop h's bucket grows to pow2(its own measured
        expansion), so a 3-hop GO's first hop does not pay the final
        hop's padding.  `uniform=True` keeps all hops at one size
        (capture_hops stacks frames along a hop axis; BFS compiles one
        per-level body).  No budget climbs past `max_cap`, unless the
        caller knows what one hop can expand at most and says so
        (`eb_cap`: a BFS level never expands more than its block's padded
        edge width, and its body carries nothing budget-wide but the
        plan), which then bounds the ladder in `max_cap`'s place.

        Returns (res, info): the fetched result and the launch's facts
        (rungs, budgets, phase timings, gate wait) that `_attribute`
        charges to each lane's statement.  Launch-level truth lands
        here, once per converged launch: the kernel ledger,
        tpu_kernel_runs and the dispatch-table slot record ONE real
        launch however many statements share it, which is precisely how
        the ledger proves the sharing is real."""
        if getattr(dev, "retired", False):
            # a re-pin or a compaction's swap gave this snapshot's
            # buffers up while we were queued at the gate: the statement
            # runs again on the one that replaced it (_on_live_snapshot)
            raise SnapshotRetired(
                "device snapshot retired by a concurrent re-pin")
        cap = self.max_cap if eb_cap is None else eb_cap
        EBs = [self.init_eb] * n_hops
        # cache key includes the seed-count (or lane-count) bucket: one
        # supernode query must not permanently inflate every later small
        # query of the same program to supernode-sized padded kernels.
        # A lane launch's also names the mesh: a 1-shard and an 8-shard
        # run of the same program have different overflow profiles
        # (per-part expansion vs whole-graph expansion)
        if lanes:
            bkey = (key_fn(()) + ("lanes", self._mesh_key()),
                    _pow2(max(len(lane_dense), 1)))
        else:
            bkey = (key_fn(()), _pow2(max(len(set(lane_dense[0])), 1)))
        prev = self._buckets.get(bkey)
        if prev is not None:
            # value kept as (0, ebs) for cache-file compat (slot 0 was
            # the old frontier bucket F); an int ebs is a legacy uniform
            pe = prev[-1]
            pe = [pe] * n_hops if isinstance(pe, int) else list(pe)
            if len(pe) == n_hops:
                EBs = [max(a, int(b)) for a, b in zip(EBs, pe)]
        if uniform:
            EBs = [max(EBs)] * n_hops

        info: Dict[str, Any] = {
            "lanes": len(lane_dense), "rungs": [], "compiles": 0,
            "refetches": 0, "gate_wait_us": wait_us, "phases": [],
            "fetch_bytes": 0, "fetch_bytes_kept": 0}
        phases, rungs = info["phases"], info["rungs"]
        with self._phase(phases, "tpu:seed_prep"):
            seed_pad, seed_fn = self._seed_frontier_prep(
                dev, lane_dense, lanes)
        L = seed_pad.shape[0] if lanes else 1
        with self._phase(phases, "device:put"), \
                self._collective_launch():
            frontier = seed_fn(seed_pad)
        info["put_s"] = phases[-1][2]

        # a post-overflow hop's reported count is a LOWER bound (its
        # frontier was truncated), so in the worst case each attempt
        # finalizes only one more hop's bucket — the retry budget must
        # scale with the hop count
        from ..utils.stats import current_work
        wc = current_work()
        for attempt in range(max(self.max_retries, n_hops + 3)):
            ebs = tuple(EBs)
            key = key_fn(ebs)
            if lanes:
                # lane suffix (not prefix): pin/unpin prune _fns by
                # key[0]==space / key[1]==epoch — lane programs must
                # age out with their snapshot like solo programs do;
                # the mesh key separates per-grid compilations
                key += ("lanes", L, self._mesh_key())
            hit = self._fns.get(key)
            compiled = hit is None
            if compiled:
                # with the program, the bytes of its 64-bit operands:
                # fixed by what it is built over (tpu_wide_operand_bytes)
                hit = self._fns[key] = (build_fn(ebs), sum(
                    a.nbytes for a in jax.tree.leaves(inputs_fn(ebs))
                    if a.dtype.itemsize == 8))
                info["compiles"] += 1
            fn, wide = hit
            # per-rung bookkeeping stays PLAIN-PYTHON here (ints and a
            # list append on locals): the dispatch neighborhood is
            # timing-sensitive under concurrent serve-while-repin (a
            # latent jaxlib CPU race); all metric/ledger emission for
            # the rungs happens once after convergence below.  A solo
            # statement's work counters see every rung as it is
            # dispatched, also of a ladder that then fails to converge
            # (a shared launch's are suppressed: _attribute)
            if wc is not None:
                wc.add("device_dispatches")
            with self._phase(phases, "device:dispatch", eb=list(EBs),
                             attempt=attempt), \
                    self._collective_launch():
                res = fn(*inputs_fn(ebs), frontier)
                jax.block_until_ready(res)
            info["device_s"] = phases[-1][2]
            rungs.append((int(info["device_s"] * 1e6), compiled))
            if "cap" in res:
                self._warm_fetch(res["cap"], key, fetch_keys, phases)
            # the rung's device buffers are released after the fetch
            # has timed itself, and the release times itself too: it
            # waits its turn for the GIL and is no part of the fetch.  A
            # failed rung's capture is so dropped BEFORE the larger rung
            # runs: holding both nearly doubles peak HBM and can fail
            # the retry
            host, held = self._fetch(res, key, fetch_keys, info)
            with self._phase(phases, "device:release"):
                res = held = None
            res = host
            if not res["ovf_expand"].any():
                break
            # hop_edges reports the true per-part pre-filter expansion
            # size PER HOP, so jump each overflowed hop STRAIGHT to its
            # needed bucket — blind doubling needs ~20 rounds for a
            # 1-seed BFS over a 30M-edge graph and times out the retry
            # budget.  (A pre-overflow hop's count is exact; a
            # post-overflow hop's is a lower bound from the truncated
            # frontier — the loop converges.)  The need is the maximum
            # over every leading axis: parts, and lanes before them
            he = np.asarray(res["hop_edges"])
            need = he.reshape(-1, he.shape[-1]).max(axis=0)
            EBs = [e if need[h] <= e else
                   min(max(2 * e, _pow2(int(need[h]))), cap)
                   for h, e in enumerate(EBs)]
            if uniform:
                EBs = [max(EBs)] * n_hops
        else:
            raise TpuUnavailable("bucket escalation did not converge")

        # the launch's accounting, once a converged launch: a phase of
        # its own, so that neither a statement's root nor a shared
        # launch's members keep it as unexplained time
        with self._phase(phases, "tpu:launch_account"):
            info["retries"], info["ebs"] = attempt, list(EBs)
            if self._buckets.get(bkey) != (0, ebs):
                self._buckets[bkey] = (0, ebs)
                # bound by evicting oldest entries — a wholesale clear()
                # would also wipe the persistent cache file on the next
                # save, re-exposing every converged query shape to the
                # recompile ladder
                while len(self._buckets) > 512:
                    self._buckets.pop(next(iter(self._buckets)))
                self._save_buckets()
            m = _metrics()
            m.inc("tpu_kernel_runs")
            edges = int(np.asarray(res["hop_edges"]).sum())
            m.inc("tpu_edges_traversed", edges)
            if kernel == "bfs":
                # what the BFS program did: the levels it ran and which of
                # them bottom-up, the slots they really expanded (in-edges
                # of the unvisited for a bottom-up level), the trips its
                # level loops ran and were budgeted (under names of their
                # own: `tpu_hop_chunks_*` are the traverse programs') and
                # the slots the level bodies RAN: a looped level's trips
                # times the trip's size, a part; a straight-line level's
                # whole budget
                run, budget = (np.asarray(res[k]).reshape(-1, n_hops)
                               for k in ("chunks_run", "chunks_budget"))
                m.inc("tpu_bfs_runs")
                m.inc("tpu_bfs_levels", n_hops)
                m.inc("tpu_bfs_levels_bottom_up",
                      int(np.asarray(res["bottom_up"]).sum()))
                m.inc("tpu_bfs_edges", edges)
                # the most slots one part expanded in one level: how far
                # past `max_cap` the deployment's levels run
                m.add_value("tpu_bfs_widest_level_slots",
                            float(np.asarray(res["hop_edges"]).max()))
                m.inc("tpu_bfs_chunks_run", int(run.sum()))
                m.inc("tpu_bfs_chunks_budget", int(budget.sum()))
                m.inc("tpu_bfs_budget_slots", int(run.sum()) * fn.chunk
                      + dev.num_parts * sum(
                          e for e, looped in zip(EBs, budget.any(axis=0))
                          if not looped))
                # a sharded program's all_gather of the frontier bitmap,
                # before each level that chooses its direction: from the
                # shapes, like the exchange's bytes below
                from .bfs import bfs_gather_bytes
                m.inc("tpu_bfs_gather_bytes", bfs_gather_bytes(
                    self.mesh_size, dev.vmax,
                    getattr(fn, "gather_levels", 0)))
            elif "chunks_run" in res:
                for k in _ENGAGEMENT:
                    m.inc(f"tpu_hop_{k}", int(res[k].sum()))
            # what pinning property columns as their halves removed: bytes
            # of 64-bit operands of the program just run, each of which a
            # chip without 64-bit lanes splits WHOLE at the top of the run
            m.add_value("tpu_wide_operand_bytes", float(wide))
            # a traverse program's per-slot gathers in its last hop's
            # expansion stage, settled when it was traced (hop.py
            # `_slot_gathers`): what a slot of the widest hop costs
            noted = getattr(fn, "noted", None)
            if noted:
                m.add_value("tpu_hop_slot_gathers",
                            float(noted["slot_gathers"]))
            m.add_value("tpu_kernel_s", info["device_s"])
            m.add_value("tpu_put_s", info["put_s"])
            m.add_value("tpu_fetch_s", info["fetch_s"])
            m.add_value("tpu_queue_s", wait_us / 1e6)
            m.inc("tpu_escalation_retries", attempt)
            m.inc("tpu_refetches", info["refetches"])
            # every byte the launch's fetches brought to the host (meta,
            # overflowed rungs and discarded speculation included) and those
            # of them that are kept capture entries
            m.inc("tpu_fetch_bytes", info["fetch_bytes"])
            m.inc("tpu_fetch_bytes_kept", info["fetch_bytes_kept"])
            # device kernel ledger (ISSUE 8 tentpole): per-RUNG dispatch µs
            # and compile-vs-cache dispositions were accumulated as plain
            # locals in the loop (every escalation rung is a real dispatch —
            # counting only the converged run would skew the ratios under
            # retries); emit them to histograms/counters HERE, outside the
            # timing-sensitive dispatch neighborhood
            for r_us, r_compiled in rungs:
                m.observe("tpu_dispatch_us", r_us, {"kernel": kernel})
                if r_compiled:
                    m.inc_labeled("tpu_kernel_compiles", {"kernel": kernel})
                else:
                    m.inc_labeled("tpu_kernel_cache_hits", {"kernel": kernel})
            hbm = info["hbm_bytes"] = self.hbm_bytes()
            self._hbm_high_water = max(
                getattr(self, "_hbm_high_water", 0), hbm)
            m.gauge("tpu_hbm_high_water_bytes", float(self._hbm_high_water))
            # per-shard dispatch/exchange facts (PR 17): the bit-packed
            # frontier all_to_all payload this converged run moved over ICI
            # — BFS exchanges every level, the traverse kernels skip the
            # final hop's exchange; a shared launch's single per-hop
            # all_to_all carries the whole L-lane payload
            xhops = n_hops if kernel == "bfs" else max(n_hops - 1, 0)
            xbytes = xhops * a2a_payload_bytes(self.mesh_size, dev.vmax, lanes=L)
            info["shards"], info["exchange_bytes"] = self.mesh_size, xbytes
            m.gauge("tpu_shards", float(self.mesh_size))
            from ..utils.flight import kernel_ledger
            kernel_ledger().record(
                kernel=kernel, shape=([L] if lanes else []) + list(EBs),
                steps=n_hops, compiled=bool(info["compiles"]),
                dispatch_us=int(info["device_s"] * 1e6), hbm_bytes=hbm,
                retries=attempt, shards=self.mesh_size, exchange_bytes=xbytes)
            if xbytes:
                m.inc("tpu_all_to_all_bytes", xbytes)
                if kernel == "bfs":
                    # the BFS programs' share, apart from the traverses'
                    m.inc("tpu_bfs_exchange_bytes", xbytes)
            # a shared launch is traced under the LAUNCHING member's
            # statement, whose context the launch suppressed: the launch
            # itself, from its seed prep to here
            with _t.use_ctx(launcher_ctx) if lanes else nullcontext():
                if lanes:
                    tp = phases[0][1]
                    _t.record_phase("tpu:batch", tp, time.perf_counter() - tp,
                                    lanes=len(lane_dense), kernel=kernel,
                                    eb=list(EBs))
                if xbytes:
                    # the exchange runs inside the fused program — its span
                    # carries payload facts, not a separate timing
                    _t.mark("tpu:shard_exchange", bytes=xbytes, hops=xhops,
                            shards=self.mesh_size,
                            **({"lanes": L} if lanes else {}))
            return res, info

    def _warm_fetch(self, cap_dev, key, fetch_keys: Optional[set],
                    phases: list):
        """Compile the fetch programs of this capture (every slice or
        piece size its width admits, on each device that holds a shard)
        when its program first runs for these columns, outside every
        timed phase: no statement meets one for the first time through
        the size of what it kept.  The one statement that does the
        compiling carries it as `tpu:fetch_warm`."""
        wk = (key, None if fetch_keys is None else frozenset(fetch_keys))
        if wk not in self._fetch_warm:
            with self._phase(phases, "tpu:fetch_warm"):
                _taker(cap_dev, fetch_keys).warm()
            if len(self._fetch_warm) > 4096:
                self._fetch_warm.clear()
            self._fetch_warm.add(wk)

    def _fetch(self, res, key, fetch_keys: Optional[set], info):
        """Bring one rung's result to the host: -> (the host result,
        what this frame still held of the device's); the launch's `info`
        takes its phases, undershoots, seconds and bytes.  It times
        itself, as its last statement, and hands the device references it
        took (the leaves, the slices cut of the capture) back to the
        caller, who holds the device result too: releasing device buffers
        waits its turn (tens of ms under eight sessions), is no part of
        the fetch and is timed by the caller as `device:release`.  The
        spans of phase `fetch` cover the clock from end to end:
        `device:fetch` the two transfers (the first with the taker's
        set-up, the second nested), `device:fetch.rows` the host's side
        of a kept capture (the pieces asked for by its kept counts, cut
        on the device and assembled into rows).

        What comes: the leaves a caller reads (`_FETCHED`) and, of the
        capture columns the yields read, each row's kept prefix
        (`_Heads`, `_Pieces`): the transfer follows the rows kept, not
        the edge budget nor the fullest row.  Two-phase on a program's
        first run, and on every run of a wide capture: the small meta
        first, then the prefixes its kept counts name.  SPECULATIVE
        single-phase for the slices after it: what the last run of this
        program (`key`) kept bounds the slice, and both phases collapse
        into ONE device_get.  An undershoot (kept grew past the
        speculation) falls back to the exact refetch and is the one
        refetch counted; an overshoot ships at most what the last run
        needed.  An overflowed rung returns meta alone, a speculative
        slice dropped."""
        t0 = time.perf_counter()
        phases = info["phases"]
        take = first = more = None
        with self._phase(phases, "device:fetch"):
            meta = {k: res[k] for k in _FETCHED if k in res}
            if "cap" in res:
                take = _taker(res["cap"], fetch_keys)
                spec = self._kmax.get(key)
                first = None if spec is None else take.speculate(spec)
            host, got = jax.device_get((meta, first))
            if first is not None:
                take.got(got)
        info["fetch_bytes"] += _nbytes(host)
        info["refetches"] = 0
        if take is not None and not host["ovf_expand"].any():
            with self._phase(phases, "device:fetch.rows"):
                kc = host["kcount"]
                more = take.ask(kc)
                if more is not None:
                    # the capture's own fetch: the second phase where
                    # nothing was speculated, else a refetch
                    info["refetches"] = int(first is not None)
                    with self._phase(phases, "device:fetch",
                                     refetch=first is not None):
                        take.got(jax.device_get(more))
                host["cap"] = take.rows(kc)
                host["cap"]["kcount"] = kc
                info["fetch_bytes_kept"] += int(kc.sum()) * take.item_bytes()
                self._kmax[key] = kc
                while len(self._kmax) > 512:
                    self._kmax.pop(next(iter(self._kmax)))
        if take is not None:
            info["fetch_bytes"] += take.nbytes
        info["fetch_s"] = time.perf_counter() - t0
        return host, (meta, take, first, more)

    @staticmethod
    def _attribute(info, res, lane: Optional[int],
                   stats: "TraverseStats", form_wait_us: int = 0):
        """Charge one statement its launch, on the statement's own
        thread: fill its TraverseStats and its thread-local
        work/cost/live sinks with its own deterministic counts (edges,
        frontier sizes) plus the launch's timings.  `lane` is None for
        a solo launch; a member of a shared launch names its lane of
        the lane-major arrays and is charged exactly what a solo
        dispatch of the same statement would have recorded."""
        def mine(k):
            a = np.asarray(res[k])
            return a if lane is None else a[lane]
        stats.hop_edges = [int(x) for x in mine("hop_edges").sum(axis=0)]
        if "frontier_sizes" in res:
            stats.frontier_sizes = [
                int(x) for x in mine("frontier_sizes").sum(axis=0)]
        for k in _ENGAGEMENT:
            if k in res:        # a BFS's levels lay no member-plan count
                setattr(stats, k, int(mine(k).sum()))
        stats.retries = info["retries"]
        stats.compiles = info["compiles"]
        stats.device_s = info["device_s"]
        stats.put_s = info["put_s"]
        stats.fetch_s = info["fetch_s"]
        stats.fetch_bytes = info["fetch_bytes"]
        stats.fetch_bytes_kept = info["fetch_bytes_kept"]
        stats.queue_s = (info["gate_wait_us"] + form_wait_us) / 1e6
        stats.f_cap, stats.e_cap = 0, list(info["ebs"])
        stats.hbm_bytes = info["hbm_bytes"]
        stats.shards = info["shards"]
        stats.exchange_bytes = info["exchange_bytes"]
        n_rungs = len(info["rungs"])
        rung_us = sum(r for r, _ in info["rungs"])
        from ..utils.stats import current_cost, current_work
        from ..utils.workload import current_live
        wc, cc, lv = current_work(), current_cost(), current_live()
        if wc is not None:
            wc.add("edges_traversed", stats.edges_traversed())
            wc.extend_frontier(stats.frontier_sizes)
        if cc is not None:
            cc.add("device_us", rung_us)
            cc.add("device_dispatches", n_rungs)
            if info["compiles"]:
                cc.add("device_compiles", info["compiles"])
        # live workload row (ISSUE 9): SHOW QUERIES reports the
        # statement's device time while it is still running
        if lv is not None:
            lv.add("device_us", rung_us)
            lv.add("dispatches", n_rungs)
        if lane is None:
            return
        # what the launch's suppression kept from this statement's
        # sinks: the rungs the driver counts as they are dispatched, the
        # queue wait `_gated_dispatch` charges, the live spans
        queue_us = int(stats.queue_s * 1e6)
        if wc is not None:
            wc.add("device_dispatches", n_rungs)
        if cc is not None:
            cc.add("queue_us", queue_us)
        if lv is not None:
            lv.add("queue_us", queue_us)
        # this lane's view of the shared launch: it waited (former +
        # gate) until the launch began (its seed prep), then the
        # launch's own phases
        t_launch = info["phases"][0][1]
        _t.record_phase("device:queue", t_launch - stats.queue_s,
                        stats.queue_s, lanes=info["lanes"])
        for name, start, dur, attrs in info["phases"]:
            _t.record_phase(name, start, dur, **attrs)

    def _statement(self, store: GraphStore, space: str,
                   vids: Sequence[Any], etypes: Sequence[str],
                   direction: str, steps: int,
                   edge_filter: Optional[E.Expr]):
        """What every device statement starts with: the pinned
        snapshot, its stats, the blocks it reads, the compiled edge
        predicate as (pred, pred_cols, pred_key) and the dense seed
        ids, under a `tpu:prep` span (the pin's `tpu:snapshot_check`
        inside it).  Raises CannotCompile if the filter does not
        vectorize."""
        t_start = time.perf_counter()
        with _t.span("tpu:prep"):
            dev = self.pin(store, space)
            sd = store.space(space)
            stats = TraverseStats()
            stats.steps = steps
            stats.pin_s = time.perf_counter() - t_start
            block_keys = [(et, d) for et in etypes for d in ("out", "in")
                          if direction in (d, "both")]
            pred: Tuple[Any, List[str], Optional[str]] = (None, [], None)
            if edge_filter is not None:
                # single-etype constraint is enforced by the optimizer rule
                bl = dev.blocks[block_keys[0]]
                pred = compile_predicate(
                    edge_filter, bl.prop_types, dev.pool,
                    vid_to_dense=sd.dense_id) + (E.to_text(edge_filter),)
            dense = [d for d in (sd.dense_id(v) for v in vids) if d >= 0]
        return t_start, dev, stats, block_keys, pred, dense

    def _block_leaves(self, dev: DeviceSnapshot, block_keys, prop_names):
        """The kernel leaves of the blocks a statement reads, the props
        among them those the program gathers, under ONE consistent delta
        view (`_grab_delta`): (view, a dict per block)."""
        dview, dextras = self._grab_delta(dev, block_keys, prop_names)
        return dview, [
            {"indptr": dev.blocks[bk].indptr, "nbr": dev.blocks[bk].nbr,
             "rank": dev.blocks[bk].rank,
             "props": {n: dev.blocks[bk].props[n] for n in prop_names},
             **(dextras[i] or {})}
            for i, bk in enumerate(block_keys)]

    def _run_traverse(self, space: str, dev: DeviceSnapshot,
                      dense: Sequence[int], block_keys, pred,
                      stats: "TraverseStats", steps: int, kernel: str,
                      capture: bool = True, yield_cols: tuple = (),
                      fetch_keys: Optional[set] = None):
        """Assemble and dispatch one traverse program (kernel
        "traverse": GO, the last hop captured; "hops": MATCH, every hop
        a frame at one uniform budget): the blocks' leaves with ONE
        consistent delta view, the program's jit key, then a shared
        launch if the batch former finds company, else a solo one; all
        of it one `tpu:launch` span, the launch's phases inside it.
        Returns (res, dview)."""
        # `tpu:launch` holds, as its own time, the host's steps around
        # the launch's phases: this assembly, the former's and the
        # gate's bookkeeping, a rung's key and program lookup, the
        # charge to the statement
        with _t.span("tpu:launch", kernel=kernel):
            pred_fn, pred_cols, pred_key = pred
            hops = kernel == "hops"
            prop_names = {n for n in pred_cols if not n.startswith("_")}
            dview, blocks = self._block_leaves(dev, block_keys,
                                               prop_names | set(yield_cols))
            blocks_data = tuple(blocks)
            if fetch_keys is not None and any(
                    _delta_rows_of(dview, bk) for bk in block_keys):
                # delta rows interleave with base rows in canonical CSR
                # order at materialize time — the host re-sort needs every
                # identity column regardless of what the yields read; a
                # plane that holds no row of these blocks adds no column
                fetch_keys |= {"src", "dst", "rank", "eidx"}
            # the program gathers and carries an edge's rank only for a
            # consumer: the fetch (all of the capture, or a yield that reads
            # rank), the predicate, a MATCH frame (edge identities), or an
            # armed delta plane, whose first row puts rank into the fetch
            # above and must not need a second program for it.  What is
            # left is a statement over an unarmed snapshot that reads none
            carry_rank = (fetch_keys is None or "rank" in fetch_keys
                          or "_rank" in pred_cols or hops
                          or any("d_src" in b for b in blocks))
            hub_dense = getattr(dev.host, "hub_dense", None)
            hub_n = 0 if hub_dense is None else len(hub_dense)

            def build(ebs, lanes=False):
                return build_traverse_fn(
                    None if self.local_mode else self.mesh, dev.num_parts,
                    ebs, steps, len(block_keys), lanes=lanes, pred=pred_fn,
                    pred_cols=pred_cols, capture=capture, capture_hops=hops,
                    yield_cols=yield_cols, carry_rank=carry_rank,
                    hub_dense=hub_dense)

            def key_fn(ebs):
                if hops:
                    return (space, dev.epoch, "hops", tuple(block_keys),
                            steps, ebs, pred_key, tuple(pred_cols), hub_n,
                            self._delta_sig(dev))
                # a program that carries rank keeps the key it always had
                # (`.tpu_buckets.json` is read across versions); the one
                # that does not shares neither program nor bucket with it
                return (space, dev.epoch, tuple(block_keys), steps, ebs,
                        pred_key, capture, tuple(pred_cols), yield_cols,
                        hub_n, self._delta_sig(dev)) + (
                            () if carry_rank else ("rank-free",))

            launch = dict(key_fn=key_fn, inputs_fn=lambda ebs: (blocks_data,),
                          n_hops=steps, uniform=hops, fetch_keys=fetch_keys,
                          kernel=kernel, stats=stats)
            # multi-lane batched dispatch (ISSUE 15): concurrent
            # compatible statements share ONE launch; None falls through
            # to the solo path (batching off / no company / capture-less
            # program)
            res = None
            if capture:
                res = self._try_batched(
                    dense, dev,
                    build_fn=functools.partial(build, lanes=True),
                    delta_epoch=dview[0] if dview is not None else None,
                    **launch)
            if res is None:
                res = self._escalate(dev, dense, build_fn=build, **launch)
        return res, dview

    @contextmanager
    def _materialising(self, stats: "TraverseStats"):
        """Row or frame assembly on the host, timed into mat_s."""
        t_mat = time.perf_counter()
        with _t.span("device:materialise"):
            yield
        stats.mat_s = time.perf_counter() - t_mat
        _metrics().add_value("tpu_mat_s", stats.mat_s)

    @_on_live_snapshot
    def traverse(self, store: GraphStore, space: str, vids: Sequence[Any],
                 etypes: Sequence[str], direction: str, steps: int,
                 edge_filter: Optional[E.Expr] = None,
                 capture: bool = True,
                 yields: Optional[List[Tuple[Any, str]]] = None
                 ) -> Tuple[List[Any], TraverseStats]:
        """Run an N-step GO expansion fully on device.

        Returns (rows, stats).  Without `yields`, rows are
        (src_vid, Edge, dst_vid) triples for every final-hop edge passing
        the predicate.  With `yields` — a list of (Expr, name) pairs the
        fusion rule verified are columnar-computable — rows are a lazy
        ColumnarDataSet holding the FINAL output as numpy columns; no
        per-row Python objects exist unless the consumer crosses the row
        boundary (the E2E fast path).  Raises CannotCompile if the
        filter does not vectorize (caller falls back to the host path).
        """
        t_start, dev, stats, block_keys, pred, dense = self._statement(
            store, space, vids, etypes, direction, steps, edge_filter)
        if not dense:
            return [], stats

        # edge props the yields read and EVERY block carries are
        # gathered on device at the compacted final-hop slots (the
        # fused-Project leg: the fetch then ships exactly the result
        # columns); props missing from some block fall back to the
        # host-side eidx gather
        yield_cols: tuple = ()
        if capture and yields is not None:
            wanted = {x.name for e, _ in yields for x in E.walk(e)
                      if x.kind == "edge_prop"
                      and not x.name.startswith("_")}
            yield_cols = tuple(sorted(
                n for n in wanted
                if all(n in dev.blocks[bk].props for bk in block_keys)))
            # each device-gathered col is one more EB-padded capture
            # buffer per block — cap the count so a wide YIELD can't
            # double peak HBM on the escalation ladder; the rest decode
            # on host via eidx as before
            if len(yield_cols) > 4:
                yield_cols = yield_cols[:4]

        # fetch only the capture arrays the yields actually read (each
        # is a kept-sized column); what none of them reads the program
        # need not carry either (`_run_traverse`: rank)
        fetch_keys = (_cap_keys_for_yields(yields, yield_cols)
                      if capture else None)
        if fetch_keys is not None and fetch_keys & {"src", "dst"} \
                and any(d == "in" for _, d in block_keys):
            # reverse blocks serve src(edge) from the dst array and vice
            # versa (physical-edge orientation) — need both
            fetch_keys |= {"src", "dst"}

        res, dview = self._run_traverse(
            space, dev, dense, block_keys, pred, stats, steps, "traverse",
            capture=capture, yield_cols=yield_cols, fetch_keys=fetch_keys)
        if not capture:
            stats.total_s = time.perf_counter() - t_start
            return [], stats

        with self._materialising(stats):
            if yields is not None:
                rows = self._materialize_yields(
                    store, space, dev, block_keys, res["cap"], yields,
                    dview=dview)
            else:
                rows = self._materialize(store, space, dev, block_keys,
                                         res["cap"], dview=dview)
        stats.result_edges = len(rows)
        stats.total_s = time.perf_counter() - t_start
        return rows, stats

    # -- MATCH device plane: layered hop frames --------------------------

    @_on_live_snapshot
    def traverse_hops(self, store: GraphStore, space: str,
                      vids: Sequence[Any], etypes: Sequence[str],
                      direction: str, max_hop: int,
                      edge_filter: Optional[E.Expr] = None
                      ) -> Tuple[List["HopFrame"], TraverseStats]:
        """Device expansion for MATCH Traverse (SURVEY §2 row 23).

        Runs max_hop frontier expansions on device with the compiled
        predicate applied at EVERY hop (MATCH edge filters are uniform
        over variable-length patterns) and captures the edge frame of
        each hop.  Returns one HopFrame per hop: the complete set of
        predicate-passing edges reachable at that depth, with Edge
        objects batch-decoded from the CSR columns.  The caller (the
        Traverse executor) assembles trail-semantics paths from the
        layered frames on host — every pred-passing edge out of any
        vertex reachable at depth d-1 is in frame d, so frame DFS with
        connectivity + distinct-edge checks enumerates exactly the paths
        the per-vertex host DFS would.

        Raises CannotCompile when the filter doesn't vectorize (caller
        may retry with edge_filter=None and re-check rows on host —
        frames are then a superset pruned during assembly).
        """
        t_start, dev, stats, block_keys, pred, dense = self._statement(
            store, space, vids, etypes, direction, max_hop, edge_filter)
        if not dense:
            return [HopFrame.empty() for _ in range(max_hop)], stats
        res, dview = self._run_traverse(
            space, dev, dense, block_keys, pred, stats, max_hop, "hops")
        with self._materialising(stats):
            frames = self._build_frames(store, space, dev, block_keys,
                                        res["cap"], max_hop, dview=dview)
        stats.result_edges = sum(f.n for f in frames)
        stats.total_s = time.perf_counter() - t_start
        return frames, stats

    def _build_frames(self, store: GraphStore, space: str,
                      dev: DeviceSnapshot, block_keys, cap, steps: int,
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
                    perms = self._delta_perms(
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

    # -- BFS (FIND SHORTEST PATH device plane) ---------------------------

    @_on_live_snapshot
    def bfs(self, store: GraphStore, space: str, srcs: Sequence[Any],
            etypes: Sequence[str], direction: str, max_steps: int,
            edge_filter: Optional[E.Expr] = None
            ) -> Tuple[np.ndarray, "TraverseStats"]:
        """Level-synchronous device BFS from `srcs`.

        Returns (dist, stats): dist is (P, Vmax) int32 of BFS depths
        (-1 unreached); the caller reconstructs paths on host (parity
        with the host oracle's multi-parent BFS).  With `edge_filter`
        (compilable predicates only — raises CannotCompile otherwise)
        the BFS only traverses mask-passing edges, matching the host
        oracle's filtered expansion.
        """
        from ..algo.frontier import LEVEL_CHUNK
        from .bfs import build_bfs_fn, build_bfs_fn_local
        _, dev, stats, block_keys, (pred, pred_cols, pred_key), dense = \
            self._statement(store, space, srcs, etypes, direction,
                            max_steps, edge_filter)
        if not dense:
            return np.full((dev.num_parts, dev.vmax), -1, np.int32), stats

        with _t.span("tpu:launch", kernel="bfs") as launch:
            P = dev.num_parts
            # direction-optimizing leg, on one chip and on a mesh: each
            # block's REVERSE twin rides along so dense levels can go
            # bottom-up (a vertex scans its in-neighbors against the
            # frontier bitmap, resident on one chip, gathered over a mesh).
            # 'both' already traverses both planes — no distinct reverse.
            rev_of = {"out": "in", "in": "out"}
            rev_keys = [(et, rev_of[d]) for et, d in block_keys
                        if d in rev_of]
            # with a delta plane armed the program itself keeps a level
            # top-down while the plane holds anything (bfs.py: bottom-up
            # scans the reverse adjacency, which the merge does not model),
            # so an armed, empty plane changes no level's direction
            have_rev = (len(rev_keys) == len(block_keys)
                        and all(rk in dev.blocks for rk in rev_keys))
            pnames = {n for n in pred_cols if not n.startswith("_")}
            _, blocks = self._block_leaves(dev, block_keys, pnames)
            if have_rev:
                for d, rk in zip(blocks, rev_keys):
                    rb = dev.blocks[rk]
                    d.update(rev_indptr=rb.indptr, rev_nbr=rb.nbr,
                             rev_rank=rb.rank,
                             rev_props={n: rb.props[n] for n in pnames})
            blocks_data = tuple(blocks)

            n_phantom = int(P * dev.vmax
                            - np.asarray(dev.num_vertices).sum())
            hub_dense = getattr(dev.host, "hub_dense", None)
            hub_n = 0 if hub_dense is None else len(hub_dense)

            def build(ebs):
                if self.local_mode:
                    return build_bfs_fn_local(P, ebs, max_steps, dev.vmax,
                                              pred=pred, pred_cols=pred_cols,
                                              have_rev=have_rev,
                                              n_phantom=n_phantom,
                                              hub_dense=hub_dense)
                return build_bfs_fn(self.mesh, P, ebs, max_steps, dev.vmax,
                                    pred=pred, pred_cols=pred_cols,
                                    have_rev=have_rev, hub_dense=hub_dense)

            # Per-LEVEL edge budgets (like the traverse kernel's per-hop
            # buckets): a BFS's first and last levels examine orders of
            # magnitude fewer edges than its middle, so one uniform bucket
            # made every level pay the widest level's padding.  The kernel
            # reports exact per-level counts, so the ladder jumps straight
            # to each level's bucket; the persistent bucket cache remembers
            # the converged shape across runs.  A level expands edges of the
            # part, each once, so its budget never has to pass the widest
            # block's padded edge width (in whole trips of the level loop,
            # which a width they do not tile would run straight-line):
            # that bounds the ladder here, not `max_cap`, under which a
            # part of more than 2^24 edges had no budget to converge to.
            width = max(int(dev.blocks[k].nbr.shape[-1]) for k in
                        list(block_keys) + (rev_keys if have_rev else []))
            if width > LEVEL_CHUNK:
                width = -(-width // LEVEL_CHUNK) * LEVEL_CHUNK
            res = self._escalate(
                dev, dense,
                key_fn=lambda ebs: (space, dev.epoch, "bfs",
                                    tuple(block_keys), max_steps, ebs,
                                    pred_key, tuple(pred_cols), have_rev,
                                    hub_n, self._delta_sig(dev)),
                build_fn=build,
                inputs_fn=lambda ebs: (blocks_data,),
                stats=stats, n_hops=max_steps, kernel="bfs", eb_cap=width)
            stats.bottom_up = [bool(b) for b in res["bottom_up"]]
            if launch is not None:
                launch["attrs"].update(levels=max_steps, eb=list(stats.e_cap),
                                       bottom_up=sum(stats.bottom_up),
                                       chunks_run=stats.chunks_run,
                                       chunks_budget=stats.chunks_budget)
        return res["dist"], stats

    # -- host materialization --------------------------------------------

    @staticmethod
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

    def _block_columns(self, store: GraphStore, space: str,
                       dev: DeviceSnapshot, block_keys, cap,
                       prop_names: Optional[Sequence[str]] = None,
                       as_np: bool = False, dview=None):
        """Vectorized gather of the captured final-hop edge set.

        Yields per-block dicts of flat numpy/object arrays: sv/dv (vids),
        rr (ranks), decoded prop columns — no per-edge Python loop; vid
        decode is one fancy-index into the dense→vid array and prop
        decode is batched per column (VERDICT r1 'weak #3' fix).

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
                perms = self._delta_perms(
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
            got, pooled = self._assemble(cap, bi, pids, perms, want, n_rows)
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
                    if as_np:
                        props[n] = decode_prop_column_np(
                            pt, raw, host.pool, has_null)
                        if pt in NUMERIC_KINDS:
                            numeric_cols += 1
                            one_pass_cols += has_null is not None
                    else:
                        props[n] = decode_prop_column(pt, raw, host.pool)
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

    @staticmethod
    def _assemble(cap, bi, pids, perms, want, n_rows):
        """The fetched pieces of block `bi`'s columns `want` ([(capture
        key, host dtype)]) joined into owned columns: -> ({key: (column,
        a property column's NULL answer)}, whether side by side).  One
        after another, a span a column (`mat_concat`), as a rule; side
        by side under ONE span where the statement is large
        (`POOL_MIN_ROWS`) and the rows keep their order (a delta plane's
        re-sort gathers a whole row first)."""
        def pieces(key):
            return _pieces([cap[key][p, bi] for p in pids], perms)

        pool = (_assembly_pool()
                if perms is None and n_rows >= POOL_MIN_ROWS else None)
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

    def _materialize(self, store: GraphStore, space: str,
                     dev: DeviceSnapshot, block_keys, cap, dview=None
                     ) -> List[Tuple[Any, Optional[Edge], Any]]:
        """(src_vid, Edge, dst_vid) triples — Edge objects built in one
        tight zip loop over pre-decoded columns."""
        rows: List[Tuple[Any, Optional[Edge], Any]] = []
        for b in self._block_columns(store, space, dev, block_keys, cap,
                                     dview=dview):
            et, etype = b["et"], b["etype"]
            names = list(b["props"])
            cols = [b["props"][n] for n in names]
            rr = b["rr"].tolist()
            for i, (sv, dv) in enumerate(zip(b["sv"].tolist(),
                                             b["dv"].tolist())):
                props = {n: c[i] for n, c in zip(names, cols)}
                rows.append((sv, Edge(sv, dv, et, rr[i], props,
                                      etype=etype), dv))
        return rows

    def _materialize_yields(self, store: GraphStore, space: str,
                            dev: DeviceSnapshot, block_keys, cap,
                            yields, dview=None) -> ColumnarDataSet:
        """Final output as a lazy columnar DataSet (fused Project).

        Columns are numpy arrays straight from the capture buffers; no
        per-row Python objects are built here — the ColumnarDataSet
        materializes rows only if the consumer crosses the row boundary
        (VERDICT r2 item 3: device results stay columnar end-to-end)."""
        needed = [x.name for e, _ in yields for x in E.walk(e)
                  if x.kind == "edge_prop"]
        per_block: List[List[np.ndarray]] = []
        for b in self._block_columns(store, space, dev, block_keys, cap,
                                     prop_names=needed, as_np=True,
                                     dview=dview):
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
