"""Builder `prebuilt_paths`: the BFS program at a real size.  As
`prebuilt_snapshot` (whose store and deployment these are, by import;
`pin_prebuilt`), over `prebuilt_mesh`'s snapshot of the `knows_symmetric`
generator's tables, so that what is pinned is what the GO proxies pin,
both directions of KNOWS among it: the reverse blocks the
direction-optimising program scans on its dense levels.  The runtime is
`TpuRuntime(n_devices=chips)`: the configuration's one chip with all its
parts on it, whatever else jax has (tier-1's eight virtual devices would
otherwise be a mesh, whose BFS has no bottom-up branch).  A request
enters at `TpuRuntime.bfs`, the call tpu/paths.py `find_shortest_device`
makes for each source; the walk back from the target is not part of it.
Sets no program flag.  Only the `bfs_levels` operation exists here."""
from __future__ import annotations

import time

import numpy as np

from benchmarks.builders.prebuilt_mesh import snapshot_from_pairs
from benchmarks.builders.prebuilt_snapshot import SPACE, Deployment, SnapshotStore
from benchmarks.lib.reply import Columns, Reply


class Session:
    def __init__(self, rt, store):
        self.rt, self.store = rt, store
        snap = store.snap
        vid = np.arange(len(snap.dense_to_vid))
        self.part, self.local = vid % snap.num_parts, vid // snap.num_parts

    def execute(self, request) -> Reply:
        t = request["template"]
        if t["op"] != "bfs_levels":
            return Reply(error=f"builder prebuilt_paths has no operation {t['op']!r}")
        try:
            dist, st = self.rt.bfs(self.store, SPACE, [request["start"]], t["over"], "out",
                                   int(t["max_steps"]))
        except Exception as ex:  # noqa: BLE001 — a refusal is a failed operation
            return Reply(error=f"{type(ex).__name__}: {ex}")
        level = np.asarray(dist)[self.part, self.local]     # vid order
        return Reply(n_rows=int((level >= 0).sum()), data=Columns({"level": level}), stats=st)

    def close(self):
        pass


class PathsDeployment(Deployment):
    def open_session(self) -> Session:
        return Session(self.rt, self.store)


def build(cfg: dict, sizes: dict, tables: dict, say) -> Deployment:
    import jax
    from nebula_tpu.tpu.runtime import TpuRuntime

    parts = int(sizes["parts"])
    t0 = time.perf_counter()
    snap = snapshot_from_pairs(tables, cfg["fixes"]["schema"]["edges"], parts, SPACE)
    build_s = time.perf_counter() - t0
    (et,) = tables["edges"]
    out = snap.blocks[(et, "out")]
    t0 = time.perf_counter()
    rt = TpuRuntime(n_devices=int(cfg["chips"]))
    assert rt.local_mode, f"one chip, not a mesh of {rt.mesh_size}"
    dev = rt.pin_prebuilt(snap)
    jax.block_until_ready(list(dev._leaves()))
    pin_s = time.perf_counter() - t0
    say(f"snapshot of {tables['n']} vertices, {int(tables['edges'][et]['src'].size)} rows, "
        f"{parts} parts of {int(out.nbr.shape[1]):,} slots built in {build_s:.1f}s; its arrays "
        f"hold {snap.hbm_bytes():,} bytes; pinned {dev.hbm_bytes():,} bytes, both directions of "
        f"{et}, in {pin_s:.1f}s; maximum out-degree {int(np.diff(out.indptr, axis=1).max())}")
    return PathsDeployment(rt, SnapshotStore(snap), {"snapshot_s": build_s, "pin_s": pin_s})
