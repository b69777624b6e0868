"""Mesh construction + pinning a CsrSnapshot into device HBM.

The partition axis of every snapshot array (axis 0, length P) is sharded
over the `'part'` mesh axis; each device holds exactly its partition's
adjacency + property columns — the device analog of the reference's
one-RocksDB-engine-per-data-path partition ownership (reference:
src/kvstore/NebulaStore [UNVERIFIED — empty mount, SURVEY §0]).
"""
from __future__ import annotations

import logging
import os
import sys
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map  # noqa: F401 — re-exported to hop.py / bfs.py
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..graphstore.csr import CsrSnapshot, StringPool
from ..graphstore.schema import PropType

_log = logging.getLogger(__name__)

#: the checkout root (parent of the `nebula_tpu` package): the default
#: home of the persistent compile cache
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class TpuUnavailable(Exception):
    """The device plane cannot serve this space/config; callers fall back
    to the host execution path."""


class SnapshotRetired(TpuUnavailable):
    """The snapshot a statement was assembled against gave its buffers
    up (a re-pin, a compaction's swap) before the statement reached the
    device.  The space is still served: `TpuRuntime` pins again and runs
    the statement on the snapshot that replaced it."""


def note_host_fallback(site: str, ex: BaseException) -> str:
    """Record ONE execute-time device→host fallback: the statement is
    about to be answered by the host engine with identical rows (the
    "never wrong, only absent" contract), which on a chip is the
    difference between "runs on the TPU" and "answers from Python".
    Counted in `tpu_host_fallback{site,error}` and logged at WARNING
    so a served graphd's operator — not only the handler thread's
    `last_tpu_fallback` — can see it.  A `CannotCompile` at PLAN time
    is routine and never comes through here.  Returns the cause string
    callers keep in `qctx.last_tpu_fallback`."""
    from ..utils.stats import stats
    cause = f"{type(ex).__name__}: {ex}"
    stats().inc_labeled("tpu_host_fallback",
                        {"site": site, "error": type(ex).__name__})
    _log.warning("device plane fell back to the host engine at %s: %s",
                 site, cause)
    return cause


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory.  The directory is placed from OUTSIDE: where
    JAX_COMPILATION_CACHE_DIR is set jax reads it itself and no code
    sets another; where it is not, the cache is `<checkout>/.jax_cache`
    — a fixed path, because the path is part of the cache key (a
    temporary name, a pid or a time would never hit).  Every entry is
    kept (no size or compile-time floor): a served graphd restarts
    into the programs it already compiled."""
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not d:
        d = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    _log.info("persistent compile cache at %s", d)
    return d


def device_identity() -> Dict[str, Any]:
    """The device as jax reports it — what every bench/smoke result
    line names."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu(who: str) -> Dict[str, Any]:
    """Log the device identity and refuse a platform other than `tpu`
    — unless the operator chose the host backend EXPLICITLY with
    JAX_PLATFORMS=cpu (tests, rehearsals).  A device plane that landed
    on the CPU without anyone asking for it must fail at start, not
    serve."""
    ident = device_identity()
    _log.warning("%s: device plane on platform=%s kind=%s count=%d",
                 who, ident["platform"], ident["kind"], ident["count"])
    if ident["platform"] != "tpu" and \
            os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu":
        raise RuntimeError(
            f"{who}: jax found platform {ident['platform']!r} "
            f"({ident['kind']}), not 'tpu' — set JAX_PLATFORMS=cpu to "
            f"run the device plane on the host backend on purpose")
    return ident


def init_multihost():
    """Join a multi-host jax runtime (ICI within a slice, DCN across
    hosts) when the standard coordination env is present — after this,
    `jax.devices()` is GLOBAL and make_mesh lays partitions across every
    host's chips; `shard_map` collectives then ride ICI/DCN exactly as
    on one host (SURVEY §5 distributed-comm: data plane = XLA
    collectives, never RPC).

    Controlled by NEBULA_COORDINATOR (host:port of process 0) plus
    NEBULA_NUM_PROCESSES / NEBULA_PROCESS_ID; no-op when unset,
    idempotent when called twice."""
    coord = os.environ.get("NEBULA_COORDINATOR")
    if not coord:
        return False
    missing = [k for k in ("NEBULA_NUM_PROCESSES", "NEBULA_PROCESS_ID")
               if k not in os.environ]
    if missing:
        # a plain config error, NOT TpuUnavailable: the executors treat
        # TpuUnavailable as the routine host-fallback signal, which
        # would silently mask a half-configured multi-host deployment
        raise ValueError(
            f"NEBULA_COORDINATOR is set but {missing} are not — "
            f"multi-host init needs all three")
    if getattr(init_multihost, "_done", False):
        return True
    try:
        n_proc = int(os.environ["NEBULA_NUM_PROCESSES"])
        proc_id = int(os.environ["NEBULA_PROCESS_ID"])
    except ValueError as ex:
        raise ValueError(
            f"NEBULA_NUM_PROCESSES / NEBULA_PROCESS_ID must be "
            f"integers: {ex}") from None
    try:
        jax.distributed.initialize(
            coordinator_address=coord,
            num_processes=n_proc,
            process_id=proc_id)
    except RuntimeError as ex:
        # already initialized (by the embedding app or a racing thread):
        # the runtime is up, which is all we need
        if "already" not in str(ex).lower():
            raise
    init_multihost._done = True
    return True


def make_mesh(n_devices: Optional[int] = None, devices=None) -> Mesh:
    """A 1-D 'part' mesh: one graph partition per device slot, built
    from `jax.devices()` (or the explicit list) and nothing else."""
    if devices is None:
        init_multihost()
        devices = jax.devices()
    if n_devices is None:
        n_devices = len(devices)
    if n_devices > len(devices):
        raise ValueError(f"need {n_devices} devices, have {len(devices)}")
    return Mesh(np.asarray(devices[:n_devices]), ("part",))


def make_mesh2(lanes: int = 1, parts: Optional[int] = None,
               devices=None) -> Mesh:
    """A 2-axis ``("lane", "part")`` mesh: the part axis owns one graph
    partition per column of devices, the lane axis spreads concurrent
    query lanes over rows (CSR blocks are replicated along it).

    Degrades gracefully instead of refusing: if ``lanes × parts`` devices
    are not available the lane axis collapses first (lanes → 1, the
    batched program still runs with every lane on the part row), then the
    part axis (parts → 1, single-chip local mode). A host with one device
    always yields the (1, 1) mesh.
    """
    explicit = devices is not None
    if devices is None:
        init_multihost()
        devices = jax.devices()
    devices = list(devices)
    if parts is None:
        parts = max(len(devices) // max(lanes, 1), 1)
    lanes = max(int(lanes), 1)
    parts = max(int(parts), 1)
    if lanes * parts > len(devices):
        if explicit:
            raise ValueError(
                f"need {lanes}x{parts} devices, have {len(devices)}")
        # degrade: lane axis first, then part axis
        lanes = max(len(devices) // parts, 1)
        if lanes * parts > len(devices):
            lanes, parts = 1, max(len(devices), 1)
        if parts > len(devices):
            parts = 1
    grid = np.asarray(devices[:lanes * parts]).reshape(lanes, parts)
    return Mesh(grid, ("lane", "part"))


def mesh_lanes(mesh: Mesh) -> int:
    """Lane-axis size of a mesh; 1 for legacy 1-D 'part' meshes."""
    return int(dict(mesh.shape).get("lane", 1))


def mesh_parts(mesh: Mesh) -> int:
    return int(dict(mesh.shape).get("part", 1))


def split_halves(col: np.ndarray) -> np.ndarray:
    """A 64-bit property column `(..., E)` as its two 32-bit bit halves
    `(..., 2, E)` `uint32`, low half first: what a chip works on in the
    column's place.

    A TPU has no 64-bit lanes: a program handed a 64-bit operand splits
    the WHOLE operand into such a pair at the top of every run (two
    column-sized passes a column, whatever the statement reads of it).
    Edge property columns therefore live on the device as the pair and
    nowhere as a 64-bit array (`pin_snapshot`, `put_delta_blocks`); the
    host snapshot keeps its 64-bit columns.  One rule for both dtypes a
    column has (csr.py `_col_dtype`): an `int64` (ints, bools, string
    codes, temporal) and a `float64` alike travel as their bits, so a
    carried value comes back to the bit on every backend.  hop.py
    `take_halves` gathers a pair, `join_halves` rebuilds a gathered
    slot's 64-bit value for a predicate, assemble.py `_join_halves`
    joins fetched halves on the host."""
    if col.dtype.itemsize != 8 or sys.byteorder != "little":
        raise TypeError(f"not a 64-bit little-endian column: {col.dtype}")
    w = np.ascontiguousarray(col).view(np.uint32).reshape(col.shape + (2,))
    return np.ascontiguousarray(np.moveaxis(w, -1, -2))


def nan_halves(pair):
    """Which gathered slots of a `float64` column's halves hold a NaN
    (the column's NULL), read off the bits: on every backend the stored
    double's own answer."""
    lo, hi = pair[..., 0, :], pair[..., 1, :]
    return ((hi & 0x7FF00000) == 0x7FF00000) & (((hi & 0xFFFFF) | lo) != 0)


def _double_of_halves(pair):
    """The double a chip computes with for a stored double's bit halves:
    `float64(h) + float64(l) + float64(r)`, three float32 worked out of
    the bits in 32-bit integer arithmetic.  `h` is the double rounded to
    float32 (nearest, ties to even) and `l` the rest rounded the same
    way: the pair a host-to-chip transfer makes of a double (numpy:
    `h = float32(x)`, `l = float32(x - float64(h))`), which is what a
    chip without 64-bit lanes holds for a `float64` and what a predicate
    read before the columns were pinned as halves.  `r` is what `l`
    leaves, so where doubles are native the sum is the stored double to
    the bit.  Outside float32's range as the transfer has it: an
    infinity over it, zero under its smallest normal number; a NaN stays
    one (`nan_halves` is the NULL test, not this value)."""
    lo, hi = pair[..., 0, :], pair[..., 1, :]
    i32, f32 = jnp.int32, jnp.float32
    sign = hi & 0x80000000
    e = ((hi >> 20) & 0x7FF).astype(i32)          # the double's exponent field
    top = ((hi & 0xFFFFF) << 3) | (lo >> 29) | 0x800000   # leading 24 bits
    rest = (lo & 0x1FFFFFFF).astype(i32)          # the 29 under them
    up = (rest > (1 << 28)) | ((rest == (1 << 28)) & ((top & 1) == 1))
    # float32's exponent field is e - 896; a significand that rounds up
    # to 2^24 carries into it
    sig = top.astype(i32) + up
    e32 = e - 896 + (sig >> 24)
    over, under = e32 >= 255, e32 <= 0  # over: NaN and infinity too
    hbits = ((jnp.clip(e, 896, 1151) - 896) << 23) + (sig - 0x800000)
    hbits = jnp.where(nan_halves(pair), 0x7FC00000,
                      jnp.where(over, 0x7F800000,
                                jnp.where(under, 0, hbits)))
    h = jax.lax.bitcast_convert_type(sign | hbits.astype(jnp.uint32), f32)
    # the rest in units of 2^(e - 1075), at most 2^28 either way, then
    # scaled by two exact powers of two (one alone leaves the range)
    d = rest - (up.astype(i32) << 29)
    d1 = d.astype(f32)
    d2 = (d - d1.astype(i32)).astype(f32)
    k = e - 1075
    ka = jnp.clip(k, -126, 127)
    kb = jnp.clip(k - ka, -126, 0)
    scale = (jax.lax.bitcast_convert_type((ka + 127) << 23, f32),
             jax.lax.bitcast_convert_type((kb + 127) << 23, f32),
             jnp.where(sign != 0, f32(-1), f32(1)))
    keep = ~(over | under)
    out = h.astype(jnp.float64)
    for part in (d1, d2):
        for m in scale:
            part = part * m
        out = out + jnp.where(keep, part, f32(0)).astype(jnp.float64)
    return out


def join_halves(pair, dtype):
    """The 64-bit values (`dtype`: int64 or float64) of gathered halves
    `(..., 2, n)` -> `(..., n)` inside a device program, what a compiled
    predicate reads (exprjit.py).  Per gathered slot, never over a
    column.  An integer is the shift-or of its halves.  A double is
    built from 32-bit pieces (`_double_of_halves`): a chip without
    64-bit lanes has no exact reinterpretation of 64 bits as one."""
    if jnp.dtype(dtype) == jnp.float64:
        return _double_of_halves(pair)
    lo, hi = pair[..., 0, :], pair[..., 1, :]
    bits = (hi.astype(jnp.uint64) << 32) | lo.astype(jnp.uint64)
    return jax.lax.bitcast_convert_type(bits, dtype)


@dataclass
class DeviceBlock:
    """One (edge type, direction) CSR block resident on the mesh."""
    etype: str
    direction: str
    indptr: Any                       # (P, Vmax+1) i32, sharded on axis 0
    nbr: Any                          # (P, Emax)   i32
    rank: Any                         # (P, Emax)   i32
    # (P, 2, Emax) u32: a column's 32-bit halves (`split_halves`)
    props: Dict[str, Any] = field(default_factory=dict)
    prop_types: Dict[str, PropType] = field(default_factory=dict)


@dataclass
class DeviceTag:
    tag: str
    present: Any                      # (P, Vmax) bool
    props: Dict[str, Any] = field(default_factory=dict)   # (P, Vmax)
    prop_types: Dict[str, PropType] = field(default_factory=dict)


@dataclass
class DeviceDelta:
    """Device-resident delta-CSR buffers for one pinned snapshot
    (ISSUE 19): per (etype, direction) block, padded insert rows +
    sorted tombstoned base edge indices, re-put whole per commit group
    (small: (P, Dcap)/(P, Tcap)).  `host` is the numpy mirror
    (graphstore.delta.HostDelta) the arrays are rebuilt from."""
    host: Any
    # bk → {"d_src","d_dst","d_rank","d_valid","d_tomb": device arrays,
    #        "d_props": {name: device array, the column's 32-bit halves
    #                    (P, 2, Dcap) as a block's props},
    #        "np": the numpy block_arrays dict these were put from,
    #        "rows": live delta rows per part when these were put}
    blocks: Dict[Tuple[str, str], Dict[str, Any]] = field(
        default_factory=dict)
    applied_epoch: int = 0            # store epoch the delta covers
    epoch: int = 0                    # bumped per device apply (jit/batch
    #                                   compatibility keys carry it; the
    #                                   BASE epoch stays fixed, so XLA
    #                                   programs and caches survive)
    # (epoch, blocks) published as ONE tuple: dispatch assembly runs
    # outside the gate, so it must grab a mutually-consistent pair —
    # an apply REPLACES the blocks dict (copy-on-write) and then swaps
    # this tuple in one atomic attribute write
    view: Tuple[int, Dict[Tuple[str, str], Dict[str, Any]]] = (0, None)

    def _leaves(self):
        for arrs in self.blocks.values():
            for k, v in arrs.items():
                if k == "d_props":
                    yield from v.values()
                elif k not in ("np", "rows"):
                    yield v


@dataclass
class DeviceSnapshot:
    """Epoch-tagged device-resident copy of one space."""
    space: str
    epoch: int
    num_parts: int
    vmax: int
    mesh: Mesh
    num_vertices: Any                 # (P,) i32
    blocks: Dict[Tuple[str, str], DeviceBlock] = field(default_factory=dict)
    tags: Dict[str, DeviceTag] = field(default_factory=dict)
    pool: StringPool = field(default_factory=StringPool)
    host: Optional[CsrSnapshot] = None   # kept for vid decode / oracle
    # uid of the SpaceData this snapshot was pinned from (None when the
    # accessor has no uid — cluster views, prebuilt bench snapshots);
    # guards the runtime's per-space cache across distinct stores
    space_uid: Optional[int] = None

    # device delta-CSR (ISSUE 19); None = delta plane off for this pin
    delta: Optional[DeviceDelta] = None

    # set by runtime.pin when a newer epoch replaced this snapshot and its
    # device buffers were donated (deleted); dispatch paths check it under
    # the read gate and fall back instead of touching dead buffers
    retired: bool = False

    def block(self, etype: str, direction: str = "out") -> DeviceBlock:
        return self.blocks[(etype, direction)]

    def _leaves(self):
        yield self.num_vertices
        for b in self.blocks.values():
            yield b.indptr
            yield b.nbr
            yield b.rank
            yield from b.props.values()
        for t in self.tags.values():
            yield t.present
            yield from t.props.values()
        if self.delta is not None:
            yield from self.delta._leaves()

    def hbm_bytes(self) -> int:
        return sum(a.nbytes for a in self._leaves())

    def shard_hbm_bytes(self) -> Dict[int, int]:
        """Per-shard HBM ledger: bytes resident on each part-axis shard.

        Every snapshot leaf is (P, ...) with axis 0 sharded (or, in
        single-chip mode, wholly resident on the one device), so each
        part's share is exactly nbytes / P per leaf — lane-axis replicas
        are not double counted (they are copies of the same partition).
        """
        P = max(int(self.num_parts), 1)
        if mesh_parts(self.mesh) == 1:
            return {0: self.hbm_bytes()}
        per = {p: 0 for p in range(P)}
        for a in self._leaves():
            share = a.nbytes // P
            for p in range(P):
                per[p] += share
        return per

    def delete_buffers(self) -> None:
        """Donate this snapshot's device buffers back to the allocator
        (re-pin path: the old epoch is freed BEFORE the new epoch is
        placed, so peak HBM stays ~1x instead of 2x). Idempotent."""
        self.retired = True
        for a in self._leaves():
            try:
                a.delete()
            except Exception:
                pass


def make_putter(mesh: Mesh, num_parts: int):
    """The placement closure shared by full pins and delta applies:
    single-chip mode puts whole arrays on the one device; multi-part
    mode puts partition p's row directly onto column-p device(s) and
    assembles with make_array_from_single_device_arrays (no host-side
    concat, no all-device broadcast copy), replicated down the lane
    axis.  `put(a, prep)` places `prep` of the array, a part at a time
    where parts travel apart: the host never holds `prep` of a whole
    sharded column (`split_halves` of 180 M values)."""
    P = mesh_parts(mesh)
    L = mesh_lanes(mesh)
    if P == 1:
        # single-chip mode: every partition resident on the one device;
        # the local (vmap) kernel runs the same program without ICI
        dev0 = mesh.devices.reshape(-1)[0]

        def put(a: np.ndarray, prep=None):
            return jax.device_put(a if prep is None else prep(a), dev0)
        return put
    if num_parts == P:
        part0 = NamedSharding(mesh, PartitionSpec("part"))
        grid = mesh.devices.reshape(L, P)

        def put(a: np.ndarray, prep=None):
            shards = [None] * (L * P)
            for p in range(P):                   # one partition per column
                row = a[p:p + 1] if prep is None else prep(a[p:p + 1])
                for lane in range(L):            # lane replicas
                    shards[lane * P + p] = jax.device_put(row, grid[lane][p])
            return jax.make_array_from_single_device_arrays(
                (P,) + row.shape[1:], part0, shards)
        return put
    raise TpuUnavailable(
        f"snapshot has {num_parts} parts but mesh has {P} devices; "
        f"create the space with partition_num == mesh size to pin it")


def put_delta_blocks(dev: DeviceSnapshot, host_delta,
                     block_keys=None) -> int:
    """(Re-)place delta buffers for `block_keys` (None = all blocks) of
    a pinned snapshot; returns bytes transferred.  Replaced buffers are
    NOT force-deleted: a batch group formed just before this apply may
    still hold references to them in its launch closure (there is no
    `retired` divert for an in-place delta apply, unlike a full
    re-pin), so the old copies are released by refcount instead —
    they are commit-group-sized, not graph-sized."""
    put = make_putter(dev.mesh, dev.num_parts)
    if dev.delta is None:
        dev.delta = DeviceDelta(host=host_delta,
                                applied_epoch=dev.epoch)
    dd = dev.delta
    keys = list(dev.blocks if block_keys is None else block_keys)
    new_blocks = dict(dd.blocks)       # copy-on-write: see DeviceDelta.view
    moved = 0
    for bk in keys:
        arrs = host_delta.block_arrays(bk)
        placed: Dict[str, Any] = {
            "np": arrs, "rows": [len(per) for per in host_delta.ins[bk]]}
        for k, v in arrs.items():
            if k == "d_props":
                placed[k] = {n: put(a, split_halves) for n, a in v.items()}
                moved += sum(a.nbytes for a in v.values())
            else:
                placed[k] = put(v)
                moved += v.nbytes
        new_blocks[bk] = placed
    dd.epoch += 1
    dd.blocks = new_blocks
    dd.view = (dd.epoch, new_blocks)
    return moved


def pin_snapshot(snap: CsrSnapshot, mesh: Mesh) -> DeviceSnapshot:
    """device_put every snapshot array, sharded over the 'part' axis.

    The snapshot's partition count must equal the mesh part-axis size —
    the 1:1 partition↔chip contract (SURVEY §2b, partition parallelism
    row). Multi-part placement is per-device: partition p's row is put
    directly onto the column-p device(s) and assembled with
    `make_array_from_single_device_arrays`, so no host-side concat and
    no all-device broadcast copy ever materialises. On a 2-axis
    ("lane", "part") mesh the CSR rows are replicated down each lane-axis
    column (each lane row sees its own resident copy of partition p).

    An edge property column is placed as its 32-bit halves
    (`split_halves`), the same bytes.  A tag's columns stay 64-bit: no
    device program takes one as an operand (exprjit.py and pipeline.py
    read them from the host snapshot).
    """
    put = make_putter(mesh, snap.num_parts)
    dev = DeviceSnapshot(space=snap.space, epoch=snap.epoch,
                         num_parts=snap.num_parts, vmax=snap.vmax, mesh=mesh,
                         num_vertices=put(snap.num_vertices),
                         pool=snap.pool, host=snap)
    for key, b in snap.blocks.items():
        dev.blocks[key] = DeviceBlock(
            etype=b.etype, direction=b.direction,
            indptr=put(b.indptr), nbr=put(b.nbr), rank=put(b.rank),
            props={k: put(v, split_halves) for k, v in b.props.items()},
            prop_types=dict(b.prop_types))
    for name, t in snap.tags.items():
        dev.tags[name] = DeviceTag(
            tag=name, present=put(t.present),
            props={k: put(v) for k, v in t.props.items()},
            prop_types=dict(t.prop_types))
    return dev
