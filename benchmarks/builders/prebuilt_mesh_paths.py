"""Builder `prebuilt_mesh_paths`: the sharded BFS program at a size one
chip refuses.  `prebuilt_paths`' session and reply (a request enters at
`TpuRuntime.bfs`, the call tpu/paths.py `find_shortest_device` makes for
each source; the reply is the level of every vertex in vid order) over
`prebuilt_mesh`'s snapshot of the `knows_symmetric` generator's tables,
pinned one part per chip by `TpuRuntime(n_devices=parts)`: a mesh of
exactly `parts` devices, whatever else jax has, so that every launch is
the `shard_map` program whose candidates go to their owners by the
bit-packed `all_to_all` after every level.  Sets no program flag and
sends nothing before the first request.  Only the BFS operation exists
here, under either of its reference operations' names.

A statement the runtime cannot answer is NOT a failed operation here, and
the session does not turn it into one: with one session over a read-only
snapshot no error comes from load, so an error is a program that cannot
run the deployment (before PR 43: `TpuUnavailable: bucket escalation did
not converge`, from every statement, eight launches each).  It ends the
run at the first statement, with the traceback and a non-zero exit code,
instead of a warm-up and a window that time the failures."""
from __future__ import annotations

import numpy as np

from benchmarks.builders import prebuilt_mesh
from benchmarks.builders.prebuilt_paths import Session as PathsSession
from benchmarks.builders.prebuilt_snapshot import SPACE, Deployment
from benchmarks.lib.reply import Columns, Reply

OPS = ("bfs_levels", "bfs_levels_wide")


class Session(PathsSession):
    def execute(self, request) -> Reply:
        t = request["template"]
        if t["op"] not in OPS:
            return Reply(error=f"builder prebuilt_mesh_paths has no operation {t['op']!r}")
        dist, st = self.rt.bfs(self.store, SPACE, [request["start"]], t["over"], "out",
                               int(t["max_steps"]))
        level = np.asarray(dist)[self.part, self.local]     # vid order
        return Reply(n_rows=int((level >= 0).sum()), data=Columns({"level": level}), stats=st)


class MeshPathsDeployment(Deployment):
    def __init__(self, rt, store, stages, say):
        super().__init__(rt, store, stages)
        self.say = say

    def open_session(self) -> Session:
        return Session(self.rt, self.store)

    def close(self):
        """Before the snapshot goes: what the program's own series say of
        the run's BFS launches (nothing where it keeps none of them), and
        each chip's peak."""
        from nebula_tpu.utils.stats import stats
        c = stats().snapshot()
        runs = c.get("tpu_bfs_widest_level_slots.count", 0)
        if runs:
            self.say(f"{int(runs)} converged BFS launches: the widest level of the fullest part "
                     f"{c['tpu_bfs_widest_level_slots.sum'] / runs:,.0f} slots in the mean; "
                     f"{int(c.get('tpu_bfs_exchange_bytes', 0) // runs):,} bytes exchanged a "
                     f"launch; trips run {int(c.get('tpu_bfs_chunks_run', 0)):,} of "
                     f"{int(c.get('tpu_bfs_chunks_budget', 0)):,} budgeted")
        self.say("peak bytes a chip: " + ", ".join(
            f"{int((d.memory_stats() or {}).get('peak_bytes_in_use', 0)):,}"
            for d in self.rt.mesh.devices.reshape(-1)))
        super().close()


def build(cfg: dict, sizes: dict, tables: dict, say) -> Deployment:
    """`prebuilt_mesh.build`'s snapshot, mesh and pin (both directions of
    the one edge type are among what it pins), under this builder's
    session."""
    dep = prebuilt_mesh.build(cfg, sizes, tables, say)
    (et,) = tables["edges"]
    rows = np.asarray(dep.store.snap.blocks[(et, "out")].indptr[:, -1], np.int64)
    say(f"rows a part {rows.tolist()}: the fullest {rows.max() / rows.mean():.4f} of the mean, "
        f"which sets every level's trips on its chip and every chip's wait at the exchange")
    return MeshPathsDeployment(dep.rt, dep.store, dep.stages, say)
