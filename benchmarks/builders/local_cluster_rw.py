"""Builder `local_cluster_rw`: `local_cluster`'s deployment (imported,
not copied) whose session sends a request as the PAIR it is — the
request's INSERT, the wait for its acknowledgement, then its read-back —
and returns the read-back's reply.  The acknowledged edge is noted with
the harness's own bookkeeping (`reference/ops/write_read.py`), never read
back from the program.  Sets no program flag.

Before the warm-up it sends ONE probe pair and reads `tpu_pins`: a
program that serves a read after a write by exporting and pinning the
whole graph again (some 20 s a pair) cannot stand this deployment up
inside a run, and the builder says so and exits non-zero there."""
from __future__ import annotations

import time

from benchmarks.builders import local_cluster
from benchmarks.lib.reply import Reply
from benchmarks.lib.requests import op_module


class Session(local_cluster.Session):
    def execute(self, request) -> Reply:
        op = op_module(request["template"]["op"])
        write = op.next_write(request)
        rs = self.client.execute(write["text"])
        if rs.error is not None:
            return Reply(error=f"{write['text'][:80]} -> {rs.error}")
        op.acknowledged(request, write)
        return super().execute(request)


class Deployment(local_cluster.Deployment):
    def open_session(self) -> Session:
        return Session(self.cluster.client())


def build(cfg: dict, sizes: dict, tables: dict, say) -> Deployment:
    from nebula_tpu.utils.stats import stats

    base = local_cluster.build(cfg, sizes, tables, say)
    dep = Deployment(base.cluster, base.tmp, base.stages)
    try:
        t0 = time.perf_counter()
        s = dep.open_session()
        try:
            probe = op_module(cfg["probe_op"]).probe_request()
            first = local_cluster.Session.execute(s, probe)   # export, pin, compile
            pins = stats().snapshot().get("tpu_pins", 0)
            t1 = time.perf_counter()
            pair = s.execute(probe)
            pair_s = time.perf_counter() - t1
            repins = stats().snapshot().get("tpu_pins", 0) - pins
        finally:
            s.close()
        for r in (first, pair):
            if r.error is not None:
                raise RuntimeError(f"probe pair: {r.error}")
        dep.stages["probe_s"] = time.perf_counter() - t0
        say(f"probe pair from {probe['start']}: first read {t1 - t0:.1f}s, then INSERT + "
            f"read-back {pair_s:.2f}s, {pair.n_rows} rows, tpu_pins +{repins}")
        if repins:
            raise SystemExit(
                f"local_cluster_rw: the read-back of ONE acknowledged write took "
                f"{pair_s:.1f}s and pinned the graph again (tpu_pins +{repins}): this "
                f"program serves a fresh read by a whole re-export, so the warm-up's pairs "
                f"alone would take {pair_s * 65 / 60:.0f} minutes. It cannot stand this "
                f"deployment up inside a run.")
    except BaseException:
        dep.close()
        raise
    return dep
