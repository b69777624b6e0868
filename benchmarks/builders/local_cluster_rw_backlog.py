"""Builder `local_cluster_rw_backlog`: `local_cluster_rw`'s deployment
(imported, not copied) after hours of uptime.  Past the probe pair a
stage `backlog_s` sends what the deployment took in since its delta
plane was last folded: acknowledged INSERTs of NEW KNOWS edges, IU8's
shape, `backlog.batch` edges a statement at most, from sources drawn as
the mix draws its own (`reference/ops/update_stream.py` `backlog`; a
rehearsal's sizes may name fewer, `backlog_sources`, so that its tiny
graph reaches the gauge with hundreds of edges instead of thousands),
each noted in the reference's book as any acknowledged write is and
each followed by ONE IS3 read so that the plane applies it — until the
program's gauge `tpu_delta_fill_ratio` (the fullest (block, part)
buffer over its capacity) reads the configuration's `backlog.fill`.  A
statement never carries more edges than the fullest buffer still lacks
rows, so the gauge is met from below and not passed.

Sets no program flag, and reads nothing back from the program but that
gauge, its capacity beside it, and `tpu_pins`: a re-pin during the
backlog is a refusal, as the probe pair's is."""
from __future__ import annotations

import time

from benchmarks.builders import local_cluster, local_cluster_rw
from benchmarks.lib.requests import op_module


def build(cfg: dict, sizes: dict, tables: dict, say) -> local_cluster_rw.Deployment:
    from nebula_tpu.utils.stats import stats

    op = op_module(cfg["probe_op"])
    dep = local_cluster_rw.build(cfg, sizes, tables, say)
    target = float(sizes.get("backlog_fill", cfg["backlog"]["fill"]))
    batch = int(cfg["backlog"]["batch"])
    sources = sizes.get("backlog_sources")      # a rehearsal's: fewer sources, fewer edges

    def gauge(name):
        return float(stats().snapshot().get(name, 0.0))
    try:
        t0 = time.perf_counter()
        pins, compactions = gauge("tpu_pins"), gauge("tpu_compactions")
        s = dep.open_session()
        sent = edges = 0
        try:
            while True:
                fill, cap = gauge("tpu_delta_fill_ratio"), gauge("tpu_delta_capacity_edges")
                lacks = int(round((target - fill) * cap))
                if lacks <= 0:
                    break
                writes, text = op.backlog(min(batch, lacks), sources)
                rs = s.client.execute(text)
                if rs.error is not None:
                    raise RuntimeError(f"backlog: {text[:80]} -> {rs.error}")
                op.backlog_acknowledged(writes)
                reply = local_cluster.Session.execute(s, op.read_of(writes[0]["src"]))
                if reply.error is not None:
                    raise RuntimeError(f"backlog read-back: {reply.error}")
                sent, edges = sent + 1, edges + len(writes)
                if gauge("tpu_pins") != pins:
                    raise SystemExit(
                        f"local_cluster_rw_backlog: the graph was pinned again during the "
                        f"backlog (tpu_pins +{gauge('tpu_pins') - pins:g} after {edges} edges, "
                        f"fill {fill:.3f}): the delta plane did not take the deployment's "
                        f"updates, so there is no plane to compact.")
        finally:
            s.close()
        dep.stages["backlog_s"] = time.perf_counter() - t0
        say(f"backlog: {edges} new edges in {sent} acknowledged INSERTs, each read back, in "
            f"{dep.stages['backlog_s']:.1f}s; tpu_delta_fill_ratio {fill:.4f} of capacity "
            f"{cap:g} (asked {target:g}), tpu_pins +0, tpu_compactions "
            f"+{gauge('tpu_compactions') - compactions:g}")
    except BaseException:
        dep.close()
        raise
    return dep
