"""TpuRuntime: what is pinned, and what runs.

Owns the mesh, the per-space DeviceSnapshots (epoch-checked against the
host store: a write bumps the space epoch, the next traversal applies
the delta or re-pins — the serve-epoch-N-while-building-N+1 model of
SURVEY §7 hard-part #6 in its simplest correct form) with their delta
planes, the jit cache keyed by bucket configuration, the seed put, the
dispatch gate, the power-of-two escalation driver around every device
program, and the statement entries (`traverse`, `traverse_hops`, `bfs`).

It is the driver of two modules that know nothing of it: what crosses
back from the device after a launch is `fetch.py`'s (`Fetcher`), and
what the caller gets of it (rows, columns, hop frames, and the result
types `TraverseStats` / `HopFrame`) is `assemble.py`'s.
"""
from __future__ import annotations

import functools
import logging
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from contextlib import contextmanager, nullcontext
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..core import expr as E
from ..graphstore.csr import build_snapshot
from ..graphstore.delta import (DeltaOverflow, DeltaUnsupported, HostDelta,
                                fold_base, pad_edge_width, pow2)
from ..graphstore.store import GraphStore
from ..utils import trace as _t
from ..utils.config import get_config
from ..utils.stats import stats as _metrics
from . import assemble
from .assemble import HopFrame, TraverseStats
from .device import (DeviceDelta, DeviceSnapshot, SnapshotRetired,
                     TpuUnavailable, make_mesh, mesh_lanes, mesh_parts, note_host_fallback,
                     pin_snapshot, put_delta_blocks)
from .exprjit import compile_predicate
from .fetch import _ENGAGEMENT, Fetcher
from .hop import a2a_payload_bytes, build_traverse_fn


_log = logging.getLogger(__name__)


@contextmanager
def statement_root(entry: str, space):
    """A device statement that arrives with no trace active (an embedded
    runtime: `pin_prebuilt`, the tools, the benchmark's proxy cells) is
    rooted as `query:tpu.<entry>`: the spans below are then live, fold
    into the phase ledger when the root closes and reach `/traces`.
    Under graphd the statement's root is active and none is opened; the
    flag is the one graphd's root obeys."""
    if _t.current_ctx() is not None or \
            not get_config().get("enable_query_tracing"):
        yield
        return
    with _t.start_trace(f"query:tpu.{entry}", service="tpu", space=space):
        yield


def _on_live_snapshot(fn):
    """The entry of a device statement (`traverse`, `traverse_hops`,
    `bfs`).  One that met a swap is served from the snapshot that
    replaced its own: `SnapshotRetired` (raised under the read gate,
    before anything ran) pins again and runs the statement anew.  Only a
    space that keeps being replaced under one statement (a few times
    over) is handed to the caller's fallback.  The statement is rooted
    HERE (`statement_root`), around its retries too."""
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
        with statement_root(entry, space):
            return attempts(self, store, space, *args, **kw)
    return run


# Which host threads a statement's row assembly may use is decided
# here, and handed to tpu/assemble.py as `_pool_for`: assembly hands a
# LARGE block's piece-passes to a few threads (a pass writes a disjoint
# slice of its column, the native join holds no GIL and neither does
# numpy's typed copy, so the pieces of a block's columns are independent
# tasks).  Under POOL_MIN_ROWS kept rows the handoff costs more than it
# buys and the serial passes run (PERF.md section 6, PR 40, has the
# sweep on the chip's host).  One pool a process, made at the first
# statement that needs it; none on a host with one core.
# POOL_MIN_ROWS, _POOL_WIDTH and _assembly_pool are the only names this
# module keeps for another's sake: tests/benchmark/test_one_pass.py
# (frozen) patches and calls them HERE, so they are read here, at call
# time, and not in the module that does the assembling.
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


def _pool_for(n_rows: int) -> Optional[ThreadPoolExecutor]:
    """The pool a block of `n_rows` kept rows is assembled by, or None
    for the serial passes."""
    return _assembly_pool() if n_rows >= POOL_MIN_ROWS else None


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
        # what crosses back after a launch, and what it remembers
        # from one launch of a program to the next (fetch.py)
        self._fetcher = Fetcher()
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
            self._fetcher.forget()
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
        cap = max(pow2(-(-width // self.DELTA_EDGE_SHARE)),
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
            self._fetcher.forget(space)
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
        shape: Tuple[int, ...] = (pow2(max([len(d) for d in ds] + [1])),)
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
            shape = (Lm * pow2(max(-(-len(ds) // Lm), 1)),) + shape
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

    def algo_dispatch(self, kernel: str, fn, *args,
                      stats: Optional[TraverseStats] = None):
        """One gated single-shot device dispatch for the algo plane
        (ISSUE 13): a vertex-program ITERATION kernel has static
        full-graph shapes — no bucket escalation, no capture fetch —
        but it rides the same gate/accounting as every other device
        program (_gated_dispatch), counts as a kernel run
        (`tpu_kernel_runs`) and additionally lands its run time
        in `tpu_dispatch_us{kernel}`, `device_us` and the SHOW QUERIES
        decomposition; `stats`, the statement's own, gains the gate's
        wait and the run.  Returns (result, dispatch_us)."""
        from ..utils.stats import current_cost, current_work
        from ..utils.workload import current_live
        with self._gated_dispatch(kernel) as wait_us:
            t0 = time.perf_counter()
            with self._collective_launch():
                res = fn(*args)
                jax.block_until_ready(res)
            us = int((time.perf_counter() - t0) * 1e6)
            _metrics().inc("tpu_kernel_runs")
            _metrics().observe("tpu_dispatch_us", us, {"kernel": kernel})
            if stats is not None:
                stats.device_s += us / 1e6
                stats.queue_s += wait_us / 1e6
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

    def algo_account(self, st: TraverseStats):
        """An algo statement's device phases, settled once a statement
        into the series every device statement's launch settles
        (`_escalate_locked`): the sums of its iterations' runs and gate
        waits, its puts, the fetch of its final state with its bytes,
        its row assembly.  Its launches have static shapes, so it never
        escalates and never fetches twice."""
        m = _metrics()
        m.add_value("tpu_kernel_s", st.device_s)
        m.add_value("tpu_put_s", st.put_s)
        m.add_value("tpu_fetch_s", st.fetch_s)
        m.add_value("tpu_queue_s", st.queue_s)
        m.add_value("tpu_mat_s", st.mat_s)
        # both stay 0: named, so that the counters exist in a process
        # that runs algo statements alone and their rates read 0 there
        m.inc("tpu_escalation_retries", 0)
        m.inc("tpu_refetches", 0)
        m.inc("tpu_fetch_bytes", st.fetch_bytes)
        m.inc("tpu_fetch_bytes_kept", st.fetch_bytes_kept)

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
                    pow2(max(len(lane_dense), 1)))
        else:
            bkey = (key_fn(()), pow2(max(len(set(lane_dense[0])), 1)))
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
        with _t.phase(phases, "tpu:seed_prep"):
            seed_pad, seed_fn = self._seed_frontier_prep(
                dev, lane_dense, lanes)
        L = seed_pad.shape[0] if lanes else 1
        with _t.phase(phases, "device:put"), \
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
            with _t.phase(phases, "device:dispatch", eb=list(EBs),
                          attempt=attempt), \
                    self._collective_launch():
                res = fn(*inputs_fn(ebs), frontier)
                jax.block_until_ready(res)
            info["device_s"] = phases[-1][2]
            rungs.append((int(info["device_s"] * 1e6), compiled))
            if "cap" in res:
                self._fetcher.warm(res["cap"], key, fetch_keys, phases)
            # the rung's device buffers are released after the fetch
            # has timed itself, and the release times itself too: it
            # waits its turn for the GIL and is no part of the fetch.  A
            # failed rung's capture is so dropped BEFORE the larger rung
            # runs: holding both nearly doubles peak HBM and can fail
            # the retry
            host, held = self._fetcher.fetch(res, key, fetch_keys, info)
            with _t.phase(phases, "device:release"):
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
                   min(max(2 * e, pow2(int(need[h]))), cap)
                   for h, e in enumerate(EBs)]
            if uniform:
                EBs = [max(EBs)] * n_hops
        else:
            raise TpuUnavailable("bucket escalation did not converge")

        # the launch's accounting, once a converged launch: a phase of
        # its own, so that neither a statement's root nor a shared
        # launch's members keep it as unexplained time
        with _t.phase(phases, "tpu:launch_account"):
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
                    assemble._delta_rows_of(dview, bk) for bk in block_keys):
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
        fetch_keys = (assemble._cap_keys_for_yields(yields, yield_cols)
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
                rows = assemble._materialize_yields(
                    store, space, dev, block_keys, res["cap"], yields,
                    _pool_for, dview=dview)
            else:
                rows = assemble._materialize(
                    store, space, dev, block_keys, res["cap"], _pool_for,
                    dview=dview)
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
            frames = assemble._build_frames(
                store, space, dev, block_keys, res["cap"], max_hop,
                dview=dview)
        stats.result_edges = sum(f.n for f in frames)
        stats.total_s = time.perf_counter() - t_start
        return frames, stats

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
