"""Builder `prebuilt_snapshot`: the device plane at a real size.  The
generator's edge arrays become a CsrSnapshot (the benchmark's copy of
nebula_tpu/bench/datagen.py `snapshot_from_arrays`: an out and an in
block per edge type, with the properties of the configuration's schema)
that `TpuRuntime().pin_prebuilt` pins; a request enters at
`TpuRuntime.traverse`, the call graphd's executor makes.  Sets no program
flag.  Only the `go` operation exists here.

The snapshot's LAYOUT is this file's, not the program's export: a change
to how the program lays out or pads a snapshot is exercised in the served
cells only (PERF.md section 7)."""
from __future__ import annotations

import time

import numpy as np

from benchmarks.lib.reply import Reply

SPACE = "snb"


def _round_up(emax: int) -> int:
    """The padded edge width of a part, rounded up to a quarter of its
    leading power of two (4,158,899 -> 4,194,304): the fullest part's edge
    count moves by a fraction of a per cent from seed to seed, and every
    compiled hop program has this width in its shapes, so without the
    rounding each new seed would compile the cell's programs anew."""
    g = 1 << max(emax.bit_length() - 3, 0)
    return -(-emax // g) * g


def _coo_to_padded_csr(owner, local, nbr_dense, vmax, P):
    counts = np.bincount(owner, minlength=P)
    emax = _round_up(max(int(counts.max()), 1))
    row_id = owner * vmax + np.minimum(local, vmax - 1)
    per_vertex = np.bincount(row_id, minlength=P * vmax).reshape(P, vmax)
    indptr = np.zeros((P, vmax + 1), np.int64)
    np.cumsum(per_vertex, axis=1, out=indptr[:, 1:])
    starts = np.zeros(P + 1, np.int64)
    np.cumsum(counts, out=starts[1:])
    pos = np.arange(owner.size, dtype=np.int64) - starts[owner]
    nbr = np.full((P, emax), -1, np.int32)
    nbr[owner, pos] = nbr_dense.astype(np.int32)
    return indptr.astype(np.int32), nbr, pos, emax, int(counts.max())


def snapshot_from_arrays(tables, schema, parts, space):
    """-> (CsrSnapshot, bytes its arrays hold, bytes they would hold
    without `_round_up`)."""
    from nebula_tpu.graphstore.csr import CsrBlock, CsrSnapshot, StringPool
    from nebula_tpu.graphstore.schema import PropType

    P, n = parts, tables["n"]
    types = {"int": PropType.INT64, "double": PropType.DOUBLE, "string": PropType.STRING}
    pool = StringPool()
    snap_bytes = unpadded_bytes = 0
    counts = np.bincount(np.arange(n, dtype=np.int64) % P, minlength=P)
    vmax = max(int(counts.max()), 1)
    snap = CsrSnapshot(space=space, epoch=0, num_parts=P, vmax=vmax,
                       num_vertices=counts.astype(np.int32), pool=pool,
                       dense_to_vid=list(range(n)))
    for et, e in tables["edges"].items():
        cols = {}
        for name, ty in schema[et].items():
            cols[name] = e[name] if ty != "string" else np.asarray(
                [pool.encode(s) for s in tables["strings"][name]], np.int64)[e[name]]
        for direction in ("out", "in"):
            a, b = (e["src"], e["dst"]) if direction == "out" else (e["dst"], e["src"])
            owner, local = a % P, a // P
            order = np.lexsort((b, local, owner))
            ow, lo, nb = owner[order], local[order], b[order]
            indptr, nbr, pos, emax, fullest = _coo_to_padded_csr(ow, lo, nb, vmax, P)
            props = {}
            for name, ty in schema[et].items():
                dt = np.float64 if ty == "double" else np.int64
                padded = np.full((P, emax), np.nan if dt == np.float64 else -2, dt)
                padded[ow, pos] = cols[name][order].astype(dt)
                props[name] = padded
            rank = np.zeros_like(nbr)
            snap.blocks[(et, direction)] = CsrBlock(
                etype=et, direction=direction, indptr=indptr, nbr=nbr, rank=rank,
                props=props, prop_types={name: types[ty] for name, ty in schema[et].items()})
            per_slot = sum(x.itemsize for x in (nbr, rank, *props.values()))
            snap_bytes += indptr.nbytes + P * emax * per_slot
            unpadded_bytes += indptr.nbytes + P * fullest * per_slot
    return snap, snap_bytes, unpadded_bytes


class SnapshotStore:
    """Just enough of a GraphStore for TpuRuntime.traverse over a
    prebuilt snapshot: dense ids, the epoch, an edge-type catalog."""

    class _SD:
        def __init__(self, n, epoch):
            self._n, self.epoch = n, epoch

        def dense_id(self, v):
            v = int(v)
            return v if 0 <= v < self._n else -1

    class _Edge:
        edge_type = 1

    class _Catalog:
        def get_edge(self, space, et):
            return SnapshotStore._Edge()

    def __init__(self, snap):
        self.snap = snap
        self._sd = SnapshotStore._SD(len(snap.dense_to_vid), snap.epoch)
        self.catalog = SnapshotStore._Catalog()

    def space(self, name):
        return self._sd


class Session:
    def __init__(self, rt, store):
        self.rt, self.store = rt, store

    def execute(self, request) -> Reply:
        from nebula_tpu.core import expr as E
        t = request["template"]
        if t["op"] != "go":
            return Reply(error=f"builder prebuilt_snapshot has no operation {t['op']!r}")
        et = t["over"][0]

        def col(c):     # `d` is the far end's id, anything else a property
            return E.FunctionCall("dst", [E.EdgeExpr()]) if c == "d" else E.EdgeProp(et, c)
        flt = None
        if t.get("w_gt") is not None:
            flt = E.Binary(">", E.EdgeProp(et, "w"), E.Literal(int(t["w_gt"])))
        try:
            rows, st = self.rt.traverse(self.store, SPACE, [request["start"]], t["over"],
                                        "out", int(t["steps"]), edge_filter=flt,
                                        yields=[(col(c), c) for c in t["cols"]])
        except Exception as ex:  # noqa: BLE001 — a refusal is a failed operation
            return Reply(error=f"{type(ex).__name__}: {ex}")
        return Reply(n_rows=len(rows), data=rows, stats=st)

    def close(self):
        pass


class Deployment:
    served = False         # no graphd: only the runtime's counters move

    def __init__(self, rt, store, stages):
        self.rt, self.store, self.stages = rt, store, stages

    def open_session(self) -> Session:
        return Session(self.rt, self.store)

    def close(self):
        self.rt.unpin(SPACE)


def build(cfg: dict, sizes: dict, tables: dict, say) -> Deployment:
    import jax
    from nebula_tpu.tpu.runtime import TpuRuntime

    t0 = time.perf_counter()
    snap, snap_bytes, unpadded_bytes = snapshot_from_arrays(
        tables, cfg["fixes"]["schema"]["edges"], int(sizes["parts"]), SPACE)
    build_s = time.perf_counter() - t0
    deg = max(int(np.diff(b.indptr, axis=1).max())
              for (_et, direction), b in snap.blocks.items() if direction == "out")
    t0 = time.perf_counter()
    rt = TpuRuntime()
    dev = rt.pin_prebuilt(snap)
    jax.block_until_ready(list(dev._leaves()))
    pin_s = time.perf_counter() - t0
    say(f"snapshot of {tables['n']} vertices, "
        f"{sum(int(e['src'].size) for e in tables['edges'].values())} edges, "
        f"{sizes['parts']} parts built in {build_s:.1f}s; its arrays hold {snap_bytes:,} bytes, "
        f"{unpadded_bytes:,} without the rounding of a part's edge width; pinned "
        f"{dev.hbm_bytes():,} bytes in {pin_s:.1f}s; maximum out-degree {deg}")
    return Deployment(rt, SnapshotStore(snap), {"snapshot_s": build_s, "pin_s": pin_s})
