"""Builder `prebuilt_mesh`: the device plane sharded one part per chip.
As `prebuilt_snapshot` (whose store, session and deployment these are, by
import) but for two things.  The runtime is `TpuRuntime(n_devices=parts)`:
a mesh of exactly `parts` devices, whatever else jax has, so that every
launch is the `shard_map` program with its frontier exchange.  And the
snapshot is built for the `knows_symmetric` generator's tables, whose row
i and row i + rows/2 are the two directions of one friendship: each part
is cut out and sorted apart, on a thread of its own, by ONE integer
argsort of a packed (local vertex, neighbour) key, and the in-block is
read off the out-block's order, because in a symmetric edge set the edges
INTO v from u sit where the edges out of v to u do, with the mirror row's
properties.  The layout is `prebuilt_snapshot`'s (a part's rows in
(local vertex, neighbour) order, width rounded up by its `_round_up`,
padding -1 / -2 / NaN); tests/benchmark/test_mesh_cell.py holds the two
builders' blocks against each other.  Sets no program flag.  Only the
`go` operation exists here."""
from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmarks.builders.prebuilt_snapshot import (SPACE, Deployment, SnapshotStore,
                                                   _round_up)

PAD = {"int": (np.int64, -2), "double": (np.float64, np.nan), "string": (np.int64, -2)}


def padded_width(rows_in_fullest_part: int) -> int:
    return _round_up(max(int(rows_in_fullest_part), 1))


def snapshot_bytes(persons: int, parts: int, width: int, schema: dict) -> int:
    """What `CsrSnapshot.hbm_bytes()` comes to for these shapes, without
    an array: `num_vertices`, and per direction an `indptr` row per part
    and, per padded slot, `nbr`, `rank` and each property."""
    vmax = -(-persons // parts)
    per_slot = 4 + 4 + sum(np.dtype(PAD[ty][0]).itemsize for ty in schema.values())
    return parts * 4 + 2 * (parts * (vmax + 1) * 4 + parts * width * per_slot)


def snapshot_from_pairs(tables, schema, parts, space):
    """-> CsrSnapshot of the one symmetric edge type in `tables`."""
    from nebula_tpu.graphstore.csr import CsrBlock, CsrSnapshot, StringPool
    from nebula_tpu.graphstore.schema import PropType

    (et, e), = tables["edges"].items()
    P, n = int(parts), int(tables["n"])
    src, dst = e["src"], e["dst"]
    half = src.size // 2
    if src.size != 2 * half or not (np.array_equal(src[:half], dst[half:])
                                    and np.array_equal(dst[:half], src[half:])):
        raise ValueError("prebuilt_mesh needs a generator whose row i + rows/2 mirrors row i")
    types = {"int": PropType.INT64, "double": PropType.DOUBLE, "string": PropType.STRING}
    pool = StringPool()
    cols = {}
    for name, ty in schema[et].items():
        cols[name] = e[name] if ty != "string" else np.asarray(
            [pool.encode(s) for s in tables["strings"][name]], np.int64)[e[name]]
    owner = (src % P).astype(np.int8)
    counts = np.bincount(owner, minlength=P)
    vmax = max(-(-n // P), 1)
    emax = padded_width(counts.max())
    indptr = np.zeros((P, vmax + 1), np.int32)
    # every page of the padded arrays is first touched by its part's thread
    nbr = np.empty((P, emax), np.int32)
    props = {d: {name: np.empty((P, emax), PAD[ty][0]) for name, ty in schema[et].items()}
             for d in ("out", "in")}

    def one_part(p):
        rows = np.flatnonzero(owner == p)
        local, far = src[rows] // P, dst[rows]
        order = np.argsort(local * n + far)
        rows, k = rows[order], rows.size
        np.cumsum(np.bincount(local, minlength=vmax), out=indptr[p, 1:])
        nbr[p, :k], nbr[p, k:] = far[order], -1
        mirror = np.where(rows < half, rows + half, rows - half)
        for name, col in cols.items():
            for d, at in (("out", rows), ("in", mirror)):
                np.take(col, at, out=props[d][name][p, :k])
                props[d][name][p, k:] = PAD[schema[et][name]][1]

    with ThreadPoolExecutor(max_workers=P) as pool_:
        list(pool_.map(one_part, range(P)))
    snap = CsrSnapshot(space=space, epoch=0, num_parts=P, vmax=vmax,
                       num_vertices=np.asarray([len(range(p, n, P)) for p in range(P)], np.int32),
                       pool=pool, dense_to_vid=list(range(n)))
    rank = np.zeros_like(nbr)
    for d in ("out", "in"):
        # the structure of a symmetric edge set is the same read either way
        snap.blocks[(et, d)] = CsrBlock(
            etype=et, direction=d, indptr=indptr, nbr=nbr, rank=rank, props=props[d],
            prop_types={name: types[ty] for name, ty in schema[et].items()})
    return snap


def build(cfg: dict, sizes: dict, tables: dict, say) -> Deployment:
    import jax
    from nebula_tpu.tpu.runtime import TpuRuntime

    parts = int(sizes["parts"])
    schema = cfg["fixes"]["schema"]["edges"]
    t0 = time.perf_counter()
    snap = snapshot_from_pairs(tables, schema, parts, SPACE)
    build_s = time.perf_counter() - t0
    (et,) = tables["edges"]
    width = int(snap.blocks[(et, "out")].nbr.shape[1])
    assert snap.hbm_bytes() == snapshot_bytes(tables["n"], parts, width, schema[et])
    deg = int(np.diff(snap.blocks[(et, "out")].indptr, axis=1).max())
    t0 = time.perf_counter()
    rt = TpuRuntime(n_devices=parts)
    assert not rt.local_mode and rt.mesh_size == parts, \
        f"a mesh of {parts} parts, not {rt.mesh_size} (local_mode {rt.local_mode})"
    dev = rt.pin_prebuilt(snap)
    jax.block_until_ready(list(dev._leaves()))
    pin_s = time.perf_counter() - t0
    per_chip = dev.shard_hbm_bytes()
    say(f"snapshot of {tables['n']} vertices, {int(tables['edges'][et]['src'].size)} rows, "
        f"{parts} parts of {width:,} slots built in {build_s:.1f}s; its arrays hold "
        f"{snap.hbm_bytes():,} bytes (snap.hbm_bytes(), what the HBM budget tests); pinned "
        f"{dev.hbm_bytes():,} bytes in {pin_s:.1f}s on a mesh of {rt.mesh_size} "
        f"({', '.join(str(d) for d in rt.mesh.devices.reshape(-1))}), per chip "
        f"{sorted(per_chip.values())}; maximum out-degree {deg}")
    return Deployment(rt, SnapshotStore(snap), {"snapshot_s": build_s, "pin_s": pin_s})
