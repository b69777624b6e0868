"""Builder `prebuilt_algo`: the vertex-program engine at a real size.  As
`prebuilt_paths` (`prebuilt_mesh`'s snapshot of a symmetric generator's
tables, `prebuilt_snapshot`'s store and deployment, by import;
`TpuRuntime(n_devices=chips).pin_prebuilt`: the configuration's one chip
whatever else jax has), with one thing more in the snapshot: a tag row for
every person, so that a person no friendship reached is a vertex to the
program as to the reference (`algo/graph.py` counts a vertex by its tag
rows and its edges' ends).

A request enters at `algo.engine.run_algorithm(func, params, snap, sd,
rt=rt)`, the call `run_call_algo` makes for a `CALL algo.*` statement,
over the snapshot and space that function's own `_host_snapshot` finds
for a device-pinned store.  `params` are the mix template's, the source
vertex in `$v`'s place; no `mode` is passed and no flag set, so the run is
the default `auto`: the device kernels, or the numpy oracle after a
fallback, which the harness's check counts.  The reply is the full
`[vid, value]` rows the call returns, row assembly included; a column of
them is made for the check only, outside the statement's time.

`close` prints what the program's own series say of the run (graph
preparation, uploads, row assembly, iterations by algorithm) and the
bytes the algo plane keeps on the device beside the chip's peak."""
from __future__ import annotations

import time
import types

import numpy as np

from benchmarks.builders.prebuilt_mesh import snapshot_from_pairs
from benchmarks.builders.prebuilt_snapshot import SPACE, Deployment, SnapshotStore
from benchmarks.lib.reply import Reply

OPS = {"pagerank": "rank", "wcc": "component", "sssp": "distance"}


class Rows:
    """The rows a statement returned, with a column of them on demand."""

    def __init__(self, rows, value):
        self.rows, self.value = rows, value

    def column(self, name):
        i = ("vid", self.value).index(name)
        integer = name in ("vid", "component")
        return np.asarray([r[i] for r in self.rows], np.int64 if integer else np.float64)


class Session:
    def __init__(self, rt, store):
        from nebula_tpu.algo.engine import _host_snapshot
        self.rt = rt
        self.snap, self.sd = _host_snapshot(
            types.SimpleNamespace(store=store, tpu_runtime=rt), SPACE)

    def execute(self, request) -> Reply:
        from nebula_tpu.algo.engine import run_algorithm
        t = request["template"]
        if t["op"] not in OPS:
            return Reply(error=f"builder prebuilt_algo has no operation {t['op']!r}")
        params = {k: request["start"] if v == "$v" else v for k, v in t["params"].items()}
        try:
            rows, info = run_algorithm(t["func"], params, self.snap, self.sd, rt=self.rt)
        except Exception as ex:  # noqa: BLE001 — a refusal is a failed operation
            return Reply(error=f"{type(ex).__name__}: {ex}")
        # the statement's own TraverseStats where the program keeps one (not the parent)
        return Reply(n_rows=len(rows), data=Rows(rows, OPS[t["op"]]), stats=info.get("stats"))

    def close(self):
        pass


class AlgoDeployment(Deployment):
    def __init__(self, rt, store, stages, say):
        super().__init__(rt, store, stages)
        self.say = say

    def open_session(self) -> Session:
        return Session(self.rt, self.store)

    def close(self):
        from nebula_tpu.utils.stats import stats
        c = stats().snapshot()

        def series(name):
            return f"{name} {c.get(name + '.sum', 0.0):.2f}s in {int(c.get(name + '.count', 0))}"
        self.say("the run's algo series: " + ", ".join(
            series(n) for n in ("algo_prepare_s", "algo_put_s", "algo_assemble_s")) + "; " + "; ".join(
            f"{a} {int(c.get(f'algo_iterations{{algo={a}}}', 0))} iterations, "
            f"{c.get(f'algo_iter_us{{algo={a}}}.sum', 0) / 1e6:.2f}s, "
            f"{int(c.get(f'algo_edge_visits{{algo={a}}}', 0)):,} edge visits" for a in OPS))
        self.say(f"tpu_algo_bytes_resident {int(c.get('tpu_algo_bytes_resident', 0)):,} beside "
                 f"tpu_hbm_bytes_pinned {int(c.get('tpu_hbm_bytes_pinned', 0)):,}; peak bytes of the "
                 f"chip " + ", ".join(
                     f"{int((d.memory_stats() or {}).get('peak_bytes_in_use', 0)):,}"
                     for d in self.rt.mesh.devices.reshape(-1)))
        super().close()


def with_tag_rows(snap, tags):
    """A tag table with a row for every vertex of the snapshot, for each
    tag of the configuration's schema (none has a property here)."""
    from nebula_tpu.graphstore.csr import TagTable
    present = np.arange(snap.vmax)[None, :] < np.asarray(snap.num_vertices)[:, None]
    for tag, props in tags.items():
        assert not props, f"prebuilt_algo lays out no tag property ({tag}: {props})"
        snap.tags[tag] = TagTable(tag=tag, present=present)
    return snap


def build(cfg: dict, sizes: dict, tables: dict, say) -> Deployment:
    import jax
    from nebula_tpu.tpu.runtime import TpuRuntime

    parts = int(sizes["parts"])
    schema = cfg["fixes"]["schema"]
    t0 = time.perf_counter()
    snap = with_tag_rows(snapshot_from_pairs(tables, schema["edges"], parts, SPACE),
                         schema["tags"])
    build_s = time.perf_counter() - t0
    (et,) = tables["edges"]
    out = snap.blocks[(et, "out")]
    t0 = time.perf_counter()
    rt = TpuRuntime(n_devices=int(cfg["chips"]))
    assert rt.local_mode, f"one chip, not a mesh of {rt.mesh_size}"
    dev = rt.pin_prebuilt(snap)
    jax.block_until_ready(list(dev._leaves()))
    pin_s = time.perf_counter() - t0
    degree = np.diff(out.indptr, axis=1)
    no_row = int((degree == 0).sum()) - (parts * snap.vmax - int(tables["n"]))
    say(f"snapshot of {tables['n']} vertices, {int(tables['edges'][et]['src'].size)} rows, "
        f"{parts} parts of {int(out.nbr.shape[1]):,} slots built in {build_s:.1f}s; its arrays "
        f"hold {snap.hbm_bytes():,} bytes; pinned {dev.hbm_bytes():,} bytes, both directions of "
        f"{et} and a {'/'.join(schema['tags'])} row a vertex, in {pin_s:.1f}s; maximum out-degree "
        f"{int(degree.max())}, {no_row} vertices without a row")
    return AlgoDeployment(rt, SnapshotStore(snap), {"snapshot_s": build_s, "pin_s": pin_s}, say)
