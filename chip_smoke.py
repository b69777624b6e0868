#!/usr/bin/env python3
"""chip_smoke.py — the served device path on one real TPU, in ONE process.

    python chip_smoke.py            # one chip: phases a + b
    python chip_smoke.py --chips 4  # four chips: phase c and nothing else

The last stdout line is one JSON object,
`{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}`,
with the device as jax reports it.  The run is ok only when the platform
is `tpu`, every phase ran, every row comparison was equal, no
execute-time device→host fallback was counted (`tpu_host_fallback`) and
no warm repeat compiled anything; otherwise the exit code is non-zero.

Where jax finds no TPU the script exits 2 at once and prints no result.
`--rehearse` runs the phases on whatever platform jax has (tiny sizes
under JAX_PLATFORMS=cpu; phase c under
XLA_FLAGS=--xla_force_host_platform_device_count=4) to find wrong
paths and shardings before a chip call — its last line says
`"ok": false` with the platform found and the exit code is 1.

Phases:
  a  served path — an in-process LocalCluster (metad, one storaged with
     raft + WAL, one graphd holding TpuRuntime()), an SNB-shaped graph
     (write_snb_csvs shapes) loaded through INSERT statements from a
     GraphClient, then GO / MATCH / FIND PATH / GET SUBGRAPH and an
     acknowledged write read back, all over the client socket; rows
     equal the numpy comparators of nebula_tpu/bench/datagen.py over an
     independently built reference store, or a runtime-less QueryEngine
     over the same cluster store where there is no comparator.
  b  device state at real size — the north-star array graph
     (make_social_arrays → snapshot_from_arrays → pin_prebuilt): 3-hop
     GO with YIELD dst, w and a 5-level BFS against host_csr_traverse /
     host_bfs by content; algo.wcc / algo.sssp / algo.pagerank on the
     device against algo/oracles.py; HBM limits.
  c  (--chips 4) the sharded plane — the phase-b graph with parts=4 on
     make_mesh(4): 3-hop GO, BFS, traverse_hops; make_mesh2(2, 2) with
     two query lanes in one launch; each against the numpy oracle and a
     1-shard runtime on device 0; per-device HBM growth and exchange
     bytes.

No subprocess that imports jax is started (the one child is g++,
building native/libnebula_native.so from source).  Data comes from
--seed; sizes are options whose defaults are the sizes above.
"""
from __future__ import annotations

import argparse
import contextlib
import csv
import json
import os
import shutil
import sys
import tempfile
import threading
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

PAGERANK_TOL = 1e-8        # documented |Δrank| bar vs the oracle


def say(msg: str) -> None:
    print(f"[smoke +{time.perf_counter() - _T0:7.1f}s] {msg}", flush=True)


_T0 = time.perf_counter()


class CompileWatch:
    """Process-wide XLA compile accounting from jax.monitoring: every
    backend compile (count, seconds) and the persistent cache's hits
    and misses — independent of the repo's own counters."""

    def __init__(self):
        from jax import monitoring
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._dur)
        monitoring.register_event_listener(self._ev)

    def _dur(self, name, secs, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _ev(self, name, **_kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def mark(self):
        return (self.compiles, self.compile_s)

    def since(self, mark):
        return self.compiles - mark[0], self.compile_s - mark[1]


class Smoke:
    def __init__(self, args, device):
        self.args = args
        self.device = device
        self.failures: list = []
        self.phases_run: list = []
        self.checks = 0
        self.device_statements = 0
        self.watch = CompileWatch()

    # -- bookkeeping ------------------------------------------------------

    def check(self, what: str, ok: bool, detail: str = "") -> bool:
        self.checks += 1
        if ok:
            say(f"  ok   {what}" + (f" — {detail}" if detail else ""))
        else:
            say(f"  FAIL {what}" + (f" — {detail}" if detail else ""))
            self.failures.append(what)
        return bool(ok)

    def phase(self, name: str, fn) -> None:
        say(f"=== phase {name} ===")
        t0 = time.perf_counter()
        try:
            fn()
        except Exception:  # noqa: BLE001 — a phase that raises fails the run
            traceback.print_exc(file=sys.stdout)
            self.failures.append(f"phase {name} raised")
        self.phases_run.append(name)
        say(f"=== phase {name} done in {time.perf_counter() - t0:.1f}s, "
            f"{len(self.failures)} failure(s) so far ===")

    def timed(self, label: str, fn, warm: int = 1):
        """Run `fn` cold (compile + escalation + pin) and `warm` more
        times; print compile seconds apart from warm seconds and the
        repo's own kernel counters; a warm repeat that compiles is a
        failure.  -> (last result, record); the COLD result (its stats
        carry the escalation retries) is kept in `self.cold`."""
        from nebula_tpu.utils.stats import stats
        s0 = stats().snapshot()
        m0 = self.watch.mark()
        t0 = time.perf_counter()
        out = self.cold = fn()
        cold_s = time.perf_counter() - t0
        cold_n, cold_cs = self.watch.since(m0)
        s1 = stats().snapshot()
        warm_s, warm_n = [], 0
        for _ in range(warm):
            m1 = self.watch.mark()
            t0 = time.perf_counter()
            out = fn()
            warm_s.append(time.perf_counter() - t0)
            warm_n += self.watch.since(m1)[0]
        s2 = stats().snapshot()

        def delta(a, b, prefix):
            return int(sum(v - a.get(k, 0) for k, v in b.items()
                           if k.startswith(prefix)))
        rec = {
            "cold_s": round(cold_s, 3),
            "xla_compiles_cold": cold_n,
            "xla_compile_s_cold": round(cold_cs, 3),
            "warm_s": [round(x, 4) for x in warm_s],
            "xla_compiles_warm": warm_n,
            "tpu_kernel_compiles": delta(s0, s1, "tpu_kernel_compiles"),
            "tpu_kernel_compiles_warm": delta(s1, s2,
                                              "tpu_kernel_compiles"),
            "tpu_kernel_runs": delta(s0, s2, "tpu_kernel_runs"),
            "edges_traversed_per_run":
                delta(s1, s2, "tpu_edges_traversed") // max(warm, 1),
        }
        say(f"  {label}: {json.dumps(rec)}")
        self.check(f"{label}: no compilation on a warm repeat",
                   warm_n == 0 and rec["tpu_kernel_compiles_warm"] == 0,
                   f"{warm_n} xla compile(s)")
        return out, rec


def counted_fallbacks(base=None) -> dict:
    """Every execute-time device→host fallback this process counted
    (since the `base` stats snapshot, when given): the run fails
    unless this is empty."""
    from nebula_tpu.utils.stats import stats
    base = base or {}
    return {k: v - base.get(k, 0) for k, v in stats().snapshot().items()
            if k.startswith("tpu_host_fallback") and v > base.get(k, 0)}


# ---------------------------------------------------------------------------
# phase a — the served path
# ---------------------------------------------------------------------------


def _rows(path):
    with open(path, newline="") as f:
        r = csv.reader(f, delimiter="|")
        next(r)
        return list(r)


def phase_a(sm: Smoke) -> None:
    import numpy as np

    from nebula_tpu.bench.datagen import (host_bfs, host_csr_traverse,
                                          host_match_agg, host_trail_paths,
                                          pick_seeds, write_snb_csvs)
    from nebula_tpu.cluster.launcher import LocalCluster
    from nebula_tpu.exec.engine import QueryEngine
    from nebula_tpu.graphstore.csr import build_snapshot
    from nebula_tpu.graphstore.store import GraphStore
    from nebula_tpu.tools import ldbc_import as ldbc
    from nebula_tpu.tpu.runtime import TpuRuntime
    from nebula_tpu.utils.stats import stats

    a = sm.args
    parts = a.parts
    tmp = tempfile.mkdtemp(prefix="chip_smoke_")
    cluster = None
    try:
        # -- data from the seed, twice over: CSVs → the reference store
        # (tools/ldbc_import) and
        # the same rows → INSERT statements for the cluster
        ppath, kpath, lpath, n_pv, n_ke, n_le = write_snb_csvs(
            tmp, a.small_persons, a.small_degree, seed=a.seed)
        say(f"small graph: {n_pv} persons, {n_ke} KNOWS, {n_le} LIKES "
            f"(seed {a.seed})")
        if (a.small_persons, a.small_degree) != (50_000, 30):
            say(f"  CUT: the bench's small graph is 50,000 persons x "
                f"degree 30; this run loads {a.small_persons} x "
                f"{a.small_degree} (the all-Python raft write path "
                f"takes in about 3-5k edges/s)")
        ref = GraphStore()
        ref.create_space("snb", partition_num=parts, vid_type="INT64")
        ldbc.import_vertices(ref, "snb",
                             f"Person:{ppath}:id,age:int,name:string",
                             "|", vid_is_int=True, header=True)
        for et, path in (("KNOWS", kpath), ("LIKES", lpath)):
            ldbc.import_edges(ref, "snb",
                              f"{et}:{path}:src,dst,w:int,f:float", "|",
                              vid_is_int=True, header=True)
        snap = build_snapshot(ref, "snb")
        sd = ref.space("snb")
        d2v = np.asarray(snap.dense_to_vid, dtype=np.int64)
        seeds = pick_seeds(ref, "snb", a.seeds, min_degree=2)
        dense = [sd.dense_id(v) for v in seeds]
        seed_list = ", ".join(str(s) for s in seeds)

        # -- the cluster: metad + storaged (raft, WAL) + graphd(TpuRuntime)
        rt = TpuRuntime()
        cluster = LocalCluster(n_meta=1, n_storage=1, n_graph=1,
                               data_dir=os.path.join(tmp, "cluster"),
                               tpu_runtime=rt)
        cl = cluster.client()

        def ex(q):
            r = cl.execute(q)
            if r.error is not None:
                raise RuntimeError(f"{q[:120]} -> {r.error}")
            return r

        ex(f"CREATE SPACE snb(partition_num={parts}, replica_factor=1, "
           f"vid_type=INT64)")
        cluster.reconcile_storage()
        for q in ("USE snb", "CREATE TAG Person(age int, name string)",
                  "CREATE EDGE KNOWS(w int, f double)",
                  "CREATE EDGE LIKES(w int, f double)"):
            ex(q)
        t0 = time.perf_counter()
        B = a.insert_batch
        rows = _rows(ppath)
        for lo in range(0, len(rows), B):
            ex("INSERT VERTEX Person(age, name) VALUES " + ", ".join(
                f'{r[0]}:({r[1]}, "{r[2]}")' for r in rows[lo:lo + B]))
        for et, path in (("KNOWS", kpath), ("LIKES", lpath)):
            rows = _rows(path)
            for lo in range(0, len(rows), B):
                ex(f"INSERT EDGE {et}(w, f) VALUES " + ", ".join(
                    f"{r[0]}->{r[1]}:({r[2]}, {r[3]})"
                    for r in rows[lo:lo + B]))
        load_s = time.perf_counter() - t0
        say(f"loaded through GraphClient INSERTs in {load_s:.1f}s "
            f"({(n_pv + n_ke + n_le) / load_s:,.0f} rows/s)")

        # the runtime-less engine over the SAME cluster store: the
        # reference where datagen has no numpy comparator
        host_eng = QueryEngine(cluster.graphds[0].store)
        hs = host_eng.new_session()
        r = host_eng.execute(hs, "USE snb")
        assert r.error is None, r.error

        def col(rs, name):
            return np.asarray(rs.data.column(name), np.int64)

        def served(label, q, compare, warm=1):
            """One device statement over the socket: cold + warm, then
            `compare(result)` -> (ok, detail)."""
            rs, rec = sm.timed(label, lambda: ex(q), warm=warm)
            sm.device_statements += 1 + warm
            sm.check(f"{label}: ran on the device",
                     rec["tpu_kernel_runs"] >= 1 + warm,
                     f"tpu_kernel_runs +{rec['tpu_kernel_runs']}")
            ok, detail = compare(rs)
            sm.check(f"{label}: rows equal the reference", ok, detail)

        def host_rows(q):
            r = host_eng.execute(hs, q)
            assert r.error is None, r.error
            return sorted(repr(x) for x in r.data.rows)

        # GO 2 STEPS
        def cmp_go2(rs):
            _, _, nxt, _w = host_csr_traverse(snap, dense, 2,
                                              materialize=True)
            want, got = np.sort(d2v[nxt]), np.sort(col(rs, "d"))
            return np.array_equal(want, got), f"{got.size} rows"
        served("GO 2 STEPS",
               f"GO 2 STEPS FROM {seed_list} OVER KNOWS "
               f"YIELD dst(edge) AS d", cmp_go2)

        # GO 3 STEPS filtered, two yield columns
        def cmp_go3f(rs):
            _, _, nxt, w = host_csr_traverse(snap, dense, 3, w_gt=50,
                                             materialize=True)
            d = d2v[nxt]
            o = np.lexsort((w, d))
            gd, gw = col(rs, "d"), col(rs, "w")
            go = np.lexsort((gw, gd))
            return (np.array_equal(d[o], gd[go])
                    and np.array_equal(w[o].astype(np.int64), gw[go]),
                    f"{gd.size} rows")
        served("GO 3 STEPS WHERE w > 50",
               f"GO 3 STEPS FROM {seed_list} OVER KNOWS "
               f"WHERE KNOWS.w > 50 YIELD dst(edge) AS d, KNOWS.w AS w",
               cmp_go3f)

        # GO 3 STEPS OVER *
        def cmp_go3all(rs):
            _, _, nxt, _w = host_csr_traverse(
                snap, dense, 3, materialize=True,
                etypes=("KNOWS", "LIKES"))
            want, got = np.sort(d2v[nxt]), np.sort(col(rs, "d"))
            return np.array_equal(want, got), f"{got.size} rows"
        served("GO 3 STEPS OVER *",
               f"GO 3 STEPS FROM {seed_list} OVER * "
               f"YIELD dst(edge) AS d", cmp_go3all)

        # IC-shaped MATCH + aggregate: the plan must be the fused node
        ic = ", ".join(str(s) for s in seeds[:4])
        q_agg = (f"MATCH (p:Person)-[:KNOWS]->(f)-[:KNOWS]->(ff:Person) "
                 f"WHERE id(p) IN [{ic}] AND ff.Person.age > 30 "
                 f"RETURN id(ff) AS v, count(*) AS c")
        plan = "\n".join(str(c) for row in ex("EXPLAIN " + q_agg).data.rows
                         for c in row)
        sm.check("MATCH agg: plan shows TpuMatchAgg", "TpuMatchAgg" in plan)

        def cmp_agg(rs):
            u, c = host_match_agg(snap, dense[:4], 30)
            gv, gc = col(rs, "v"), col(rs, "c")
            o = np.argsort(gv)
            return (np.array_equal(d2v[u], gv[o])
                    and np.array_equal(c.astype(np.int64), gc[o]),
                    f"{gv.size} groups")
        served("MATCH id(ff), count(*)", q_agg, cmp_agg)

        # MATCH *1..4 — trail paths
        vl = ", ".join(str(s) for s in seeds[:a.varlen_seeds])

        def cmp_varlen(rs):
            want = int(host_trail_paths(snap, dense[:a.varlen_seeds], 4))
            got = int(rs.data.rows[0][0])
            return want == got, f"{got} trails (oracle {want})"
        served("MATCH *1..4",
               f"MATCH (a:Person)-[e:KNOWS*1..4]->(b) "
               f"WHERE id(a) IN [{vl}] RETURN count(*) AS paths",
               cmp_varlen)

        # FIND SHORTEST PATH: a target 3 levels out, by the numpy BFS
        lv = host_bfs(snap, dense[:1], 4)
        far = np.flatnonzero(lv == min(3, int(lv.max())))
        target = int(d2v[far[0]])
        q_sp = (f"FIND SHORTEST PATH FROM {seeds[0]} TO {target} "
                f"OVER KNOWS UPTO 4 STEPS YIELD path AS p")
        want_sp = host_rows(q_sp)

        def cmp_sp(rs):
            got = sorted(repr(x) for x in rs.data.rows)
            return (got == want_sp and len(got) > 0,
                    f"{len(got)} path(s), BFS depth {int(lv[far[0]])}")
        served("FIND SHORTEST PATH", q_sp, cmp_sp)

        # GET SUBGRAPH
        q_sg = (f"GET SUBGRAPH 2 STEPS FROM {seeds[0]} OUT KNOWS "
                f"YIELD VERTICES AS v, EDGES AS e")
        want_sg = host_rows(q_sg)

        def cmp_sg(rs):
            got = sorted(repr(x) for x in rs.data.rows)
            return got == want_sg and len(got) > 0, f"{len(got)} rows"
        served("GET SUBGRAPH", q_sg, cmp_sg)

        # an acknowledged write, read back by the device
        _, _, nxt1, w1 = host_csr_traverse(snap, dense[:1], 1,
                                           materialize=True)
        have = set(d2v[nxt1].tolist())
        new_dst = next(v for v in range(a.small_persons)
                       if v not in have and v != seeds[0])
        pins0 = stats().snapshot().get("tpu_pins", 0)
        ex(f"INSERT EDGE KNOWS(w, f) VALUES "
           f"{seeds[0]}->{new_dst}:(77, 0.5)")

        def cmp_fresh(rs):
            gd, gw = col(rs, "d"), col(rs, "w")
            want_d = np.concatenate([d2v[nxt1], [new_dst]])
            want_w = np.concatenate([w1.astype(np.int64), [77]])
            o, go = np.lexsort((want_w, want_d)), np.lexsort((gw, gd))
            return (np.array_equal(want_d[o], gd[go])
                    and np.array_equal(want_w[o], gw[go])
                    and new_dst in gd.tolist(),
                    f"{gd.size} rows, acknowledged edge "
                    f"{seeds[0]}->{new_dst} present")
        served("INSERT EDGE then GO 1 STEPS",
               f"GO 1 STEPS FROM {seeds[0]} OVER KNOWS "
               f"YIELD dst(edge) AS d, KNOWS.w AS w", cmp_fresh)
        say(f"  re-pins after the write: "
            f"{stats().snapshot().get('tpu_pins', 0) - pins0}")
    finally:
        if cluster is not None:
            cluster.stop()
        shutil.rmtree(tmp, ignore_errors=True)


# ---------------------------------------------------------------------------
# phase b — device state at real size
# ---------------------------------------------------------------------------


def _north_star(sm: Smoke, parts: int, arrs=None):
    from nebula_tpu.bench.datagen import (SnapshotStore,
                                          make_social_arrays,
                                          snapshot_from_arrays)
    a = sm.args
    t0 = time.perf_counter()
    if arrs is None:
        arrs = make_social_arrays(a.persons, a.degree, seed=a.seed)
    space = f"ns{parts}"
    snap = snapshot_from_arrays(arrs, parts=parts, space=space)
    snap.space = space
    say(f"north-star graph: {a.persons} persons x degree {a.degree}, "
        f"{int(arrs['src'].size)} edges, {parts} parts, built in "
        f"{time.perf_counter() - t0:.1f}s")
    return arrs, snap, SnapshotStore(snap), space


def _go3_yields():
    from nebula_tpu.core import expr as E
    return [(E.FunctionCall("dst", [E.EdgeExpr()]), "d"),
            (E.EdgeProp("KNOWS", "w"), "w")]


def _go3_equal(rows, cpu_dst, cpu_w):
    import numpy as np
    dev_d = np.asarray(rows.column_array("d"), np.int64)
    dev_w = np.asarray(rows.column_array("w"), np.int64)
    if dev_d.size != cpu_dst.size:
        return False
    od, oc = np.lexsort((dev_w, dev_d)), np.lexsort((cpu_w, cpu_dst))
    return bool((dev_d[od] == cpu_dst[oc]).all()
                and (dev_w[od] == cpu_w[oc]).all())


def _bfs_equal(dist, np_dist, parts):
    import numpy as np
    dev = np.asarray(dist, np.int32)
    vv = np.arange(np_dist.shape[0])
    return bool(np.array_equal(dev[vv % parts, vv // parts], np_dist))


def phase_b(sm: Smoke) -> None:
    import jax
    import numpy as np

    from nebula_tpu.algo.engine import run_algorithm
    from nebula_tpu.bench.datagen import host_bfs, host_csr_traverse
    from nebula_tpu.tpu.runtime import TpuRuntime
    from nebula_tpu.utils.config import get_config
    from nebula_tpu.utils.stats import stats

    a = sm.args
    parts = a.parts
    arrs, snap, sstore, space = _north_star(sm, parts)
    deg = np.diff(snap.block("KNOWS", "out").indptr, axis=1)
    rt = TpuRuntime()
    t0 = time.perf_counter()
    dev = rt.pin_prebuilt(snap)
    jax.block_until_ready(list(dev._leaves()))
    say(f"pinned {dev.hbm_bytes():,} bytes in "
        f"{time.perf_counter() - t0:.1f}s; maximum degree "
        f"{int(deg.max())}")
    seeds = np.unique(arrs["src"][:4 * a.seeds])[:a.seeds].tolist()
    yields = _go3_yields()

    # 3-hop GO, YIELD dst, w
    (rows, st), _ = sm.timed(
        "b: GO 3 STEPS YIELD dst, w",
        lambda: rt.traverse(sstore, space, seeds, ["KNOWS"], "out", 3,
                            yields=yields))
    sm.device_statements += 2
    total, kept, cpu_dst, cpu_w = host_csr_traverse(snap, seeds, 3,
                                                    materialize=True)
    say(f"  cold: escalation retries {sm.cold[1].retries}, "
        f"{sm.cold[1].compiles} program(s) built; EB {st.e_cap}, edges "
        f"traversed {st.edges_traversed()}, result rows {len(rows)}, "
        f"warm device_s {st.device_s:.4f}")
    sm.check("b: GO 3 STEPS rows equal host_csr_traverse by content",
             total == st.edges_traversed() and kept == len(rows)
             and _go3_equal(rows, cpu_dst, cpu_w),
             f"{kept} rows, {total} edges")

    # 5-level BFS
    (dist, stb), _ = sm.timed(
        "b: BFS 5 levels",
        lambda: rt.bfs(sstore, space, seeds[:1], ["KNOWS"], "out", 5))
    sm.device_statements += 2
    np_dist = host_bfs(snap, seeds[:1], 5)
    say(f"  cold: escalation retries {sm.cold[1].retries}, "
        f"{sm.cold[1].compiles} program(s) built; edges traversed "
        f"{stb.edges_traversed()}, reached {int((np_dist >= 0).sum())}, "
        f"warm device_s {stb.device_s:.4f}")
    sm.check("b: BFS distances equal host_bfs",
             _bfs_equal(dist, np_dist, parts))

    # CALL algo.* on the device against algo/oracles.py (mode=device
    # raises instead of falling back; mode=host IS the oracle)
    sd = sstore.space(space)
    for func, params in (
            ("wcc", {}),
            ("sssp", {"src": int(seeds[0]), "weight": "w"}),
            ("pagerank", {"max_iter": a.pagerank_iters, "tol": 0.0})):
        iters = {}

        def run_dev(func=func, params=params, iters=iters):
            rows_d, info = run_algorithm(
                func, {**params, "mode": "device"}, snap, sd, rt=rt)
            iters["n"] = info["iterations"]
            return rows_d
        dev_rows, _ = sm.timed(f"b: algo.{func}", run_dev)
        t0 = time.perf_counter()
        host_rows_, _ = run_algorithm(func, {**params, "mode": "host"},
                                      snap, sd)
        oracle_s = time.perf_counter() - t0
        if func == "pagerank":
            dv = np.asarray([r[1] for r in dev_rows])
            hv = np.asarray([r[1] for r in host_rows_])
            same = [r[0] for r in dev_rows] == [r[0] for r in host_rows_]
            diff = float(np.abs(dv - hv).max()) if same else float("inf")
            ok, detail = diff <= PAGERANK_TOL, f"max |d rank| {diff:.3e}"
        else:
            ok, detail = dev_rows == host_rows_, "exact"
        sm.check(f"b: algo.{func} rows equal the oracle", ok,
                 f"{len(dev_rows)} rows, {iters['n']} iterations, "
                 f"{detail}, oracle {oracle_s:.1f}s")

    # HBM: the device's own numbers beside the repo's ledger
    ms = jax.devices()[0].memory_stats()
    pinned = stats().snapshot().get("tpu_hbm_bytes_pinned")
    flag = int(get_config().get("tpu_hbm_limit_bytes"))
    say(f"  memory_stats: bytes_limit "
        f"{ms and ms.get('bytes_limit')}, peak_bytes_in_use "
        f"{ms and ms.get('peak_bytes_in_use')}; tpu_hbm_bytes_pinned "
        f"{pinned}; tpu_hbm_limit_bytes {flag}")
    if ms and ms.get("bytes_limit"):
        sm.check("b: tpu_hbm_limit_bytes within the device's own limit",
                 flag <= int(ms["bytes_limit"]),
                 f"{flag:,} vs {int(ms['bytes_limit']):,}")
    else:
        sm.check("b: the device reports memory_stats",
                 sm.device["platform"] != "tpu",
                 "none on this platform")
    rt.unpin(space)


# ---------------------------------------------------------------------------
# phase c — four chips
# ---------------------------------------------------------------------------


def phase_c(sm: Smoke) -> None:
    import jax
    import numpy as np

    from nebula_tpu.bench.datagen import host_bfs, host_csr_traverse
    from nebula_tpu.tpu import TpuRuntime, make_mesh, make_mesh2
    from nebula_tpu.tpu.batch import batch_former
    from nebula_tpu.utils.config import get_config
    from nebula_tpu.utils.stats import stats
    from nebula_tpu.utils.workload import live_registry

    a = sm.args
    devs = jax.devices()
    if len(devs) != 4:
        raise RuntimeError(f"--chips 4 needs 4 devices, jax has "
                           f"{len(devs)}")

    def in_use():
        out = []
        for d in devs:
            ms = d.memory_stats()
            out.append(None if not ms else int(ms.get("bytes_in_use", 0)))
        return out

    arrs, snap4, sstore4, sp4 = _north_star(sm, 4)
    seeds = np.unique(arrs["src"][:4 * a.seeds])[:a.seeds].tolist()
    yields = _go3_yields()

    # -- the 1-D mesh: one partition per chip ------------------------------
    rt4 = TpuRuntime(make_mesh(4, devices=devs))
    before = in_use()
    dev4 = rt4.pin_prebuilt(snap4)
    jax.block_until_ready(list(dev4._leaves()))
    after = in_use()
    pinned = dev4.hbm_bytes()
    ledger = dev4.shard_hbm_bytes()
    say(f"pinned {pinned:,} bytes over 4 parts; ledger "
        f"{ {k: int(v) for k, v in ledger.items()} }")
    if None in before or None in after:
        sm.check("c: every device reports memory_stats",
                 sm.device["platform"] != "tpu", "none on this platform")
    else:
        grew = [y - x for x, y in zip(before, after)]
        say(f"  memory_stats bytes_in_use growth per device: {grew}")
        q = pinned / 4
        sm.check("c: each device's HBM grew by about a quarter of the "
                 "pinned bytes",
                 all(0.8 * q <= g <= 1.25 * q for g in grew),
                 f"quarter = {int(q):,}")

    rt1 = TpuRuntime(make_mesh(1, devices=devs[:1]))
    rt1.pin_prebuilt(snap4)
    total, kept, cpu_dst, cpu_w = host_csr_traverse(snap4, seeds, 3,
                                                    materialize=True)
    a2a0 = stats().snapshot().get("tpu_all_to_all_bytes", 0)

    def go3(rt):
        return rt.traverse(sstore4, sp4, seeds, ["KNOWS"], "out", 3,
                           yields=yields)
    (rows4, st4), _ = sm.timed("c: GO 3 STEPS on 4 shards",
                               lambda: go3(rt4))
    (rows1, st1), _ = sm.timed("c: GO 3 STEPS on 1 shard (device 0)",
                               lambda: go3(rt1))
    sm.device_statements += 4
    say(f"  4 shards: retries {st4.retries}, EB {st4.e_cap}, exchange "
        f"bytes {st4.exchange_bytes}; 1 shard: retries {st1.retries}")
    sm.check("c: GO rows equal the numpy oracle",
             st4.edges_traversed() == total
             and _go3_equal(rows4, cpu_dst, cpu_w), f"{kept} rows")
    sm.check("c: GO rows equal the 1-shard runtime",
             _go3_equal(rows1, cpu_dst, cpu_w) and st4.shards == 4
             and st1.shards == 1)

    np_dist = host_bfs(snap4, seeds[:1], 5)

    def bfs(rt):
        return rt.bfs(sstore4, sp4, seeds[:1], ["KNOWS"], "out", 5)
    (d4, _), _ = sm.timed("c: BFS on 4 shards", lambda: bfs(rt4))
    (d1, _), _ = sm.timed("c: BFS on 1 shard", lambda: bfs(rt1))
    sm.device_statements += 4
    sm.check("c: BFS distances equal host_bfs (4 shards and 1 shard)",
             _bfs_equal(d4, np_dist, 4) and _bfs_equal(d1, np_dist, 4))

    # MATCH-mode capture: every hop's frame, from a few seeds
    hseeds = seeds[:a.varlen_seeds]

    def hops(rt):
        return rt.traverse_hops(sstore4, sp4, hseeds, ["KNOWS"], "out", 3)
    (f4, _), _ = sm.timed("c: traverse_hops on 4 shards",
                          lambda: hops(rt4))
    (f1, _), _ = sm.timed("c: traverse_hops on 1 shard", lambda: hops(rt1))
    sm.device_statements += 4
    ok = len(f4) == len(f1) == 3
    for h in range(3 if ok else 0):
        _, _, nxt, _w = host_csr_traverse(snap4, hseeds, h + 1,
                                          materialize=True)
        want = np.sort(nxt)

        def key(fr):
            o = np.lexsort((fr.dst, fr.src))
            return fr.src[o], fr.dst[o]
        s4, t4 = key(f4[h])
        s1, t1 = key(f1[h])
        ok = ok and np.array_equal(np.sort(f4[h].dst), want) \
            and np.array_equal(s4, s1) and np.array_equal(t4, t1)
    sm.check("c: traverse_hops frames equal the numpy oracle and the "
             "1-shard runtime", ok,
             f"frame sizes {[int(f.n) for f in f4]}")
    a2a1 = stats().snapshot().get("tpu_all_to_all_bytes", 0)
    sm.check("c: tpu_all_to_all_bytes moved", a2a1 > a2a0,
             f"+{int(a2a1 - a2a0):,} bytes")
    rt4.unpin(sp4)
    rt1.unpin(sp4)

    # -- the 2 x 2 grid: two query lanes in ONE launch ---------------------
    _, snap2, sstore2, sp2 = _north_star(sm, 2, arrs=arrs)
    rtg = TpuRuntime(make_mesh2(2, 2, devices=devs))
    sm.check("c: make_mesh2(2, 2) is a 2-lane x 2-part grid",
             rtg.mesh_lanes == 2 and rtg.mesh_size == 2)
    rtg.pin_prebuilt(snap2)
    rts = TpuRuntime(make_mesh(1, devices=devs[:1]))
    rts.pin_prebuilt(snap2)
    lane_seeds = [seeds[:len(seeds) // 2], seeds[len(seeds) // 2:]]

    def go2(rt, sds):
        return rt.traverse(sstore2, sp2, sds, ["KNOWS"], "out", 2,
                           yields=yields)
    want = []
    for sds in lane_seeds:
        rows_s, _ = go2(rts, sds)
        _, _, cd, cw = host_csr_traverse(snap2, sds, 2, materialize=True)
        sm.check(f"c: grid reference lane ({len(sds)} seeds) 1-shard "
                 f"rows equal the numpy oracle",
                 _go3_equal(rows_s, cd, cw), f"{cd.size} rows")
        want.append((cd, cw))
        go2(rtg, sds)                 # solo on the grid: warms the seed put
    sm.device_statements += 4
    batch_former().reset()
    # the former opens a window only on evidence of concurrency: two
    # live statements, as a served graphd would have
    for i in range(2):
        live_registry().register(qid=-(900 + i), session=0, user="smoke",
                                 stmt="lane", kind="Go")
    get_config().set_dynamic_many({"batch_max_lanes": 2,
                                   "batch_wait_us": 5_000_000})
    s0 = stats().snapshot()
    got, errs = {}, []

    def lane(i):
        try:
            got[i] = go2(rtg, lane_seeds[i])
        except Exception as ex_:  # noqa: BLE001 — reported by the check
            errs.append(repr(ex_))
    try:
        ths = [threading.Thread(target=lane, args=(i,)) for i in range(2)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(900)
    finally:
        get_config().set_dynamic_many({"batch_max_lanes": 0,
                                       "batch_wait_us": 1500})
        for i in range(2):
            live_registry().deregister(-(900 + i))
        batch_former().reset()
    s1 = stats().snapshot()
    sm.device_statements += 1
    formed = s1.get("tpu_batches_formed", 0) \
        - s0.get("tpu_batches_formed", 0)
    sm.check("c: two lanes shared one launch on the 2 x 2 grid",
             not errs and len(got) == 2 and formed >= 1,
             f"tpu_batches_formed +{int(formed)} {errs[:1]}")
    for i in range(2):
        if i in got:
            sm.check(f"c: lane {i} rows equal the numpy oracle and the "
                     f"1-shard runtime",
                     _go3_equal(got[i][0], *want[i]),
                     f"{want[i][0].size} rows")
    rtg.unpin(sp2)
    rts.unpin(sp2)


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = the sharded phase c and no other phase")
    ap.add_argument("--rehearse", action="store_true",
                    help="run the phases on a platform other than tpu "
                         "(never ok: exit 1)")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--parts", type=int, default=8)
    ap.add_argument("--seeds", type=int, default=16)
    # phase a: the bench's small graph is 50,000 x 30 (1.5M KNOWS +
    # 0.3M LIKES); the all-Python raft write path takes in ~3-5k
    # edges/s, so the default keeps the degree (the fan-out shape) and
    # cuts the persons until the load fits in about two minutes
    ap.add_argument("--small-persons", type=int, default=10_000)
    ap.add_argument("--small-degree", type=int, default=30)
    ap.add_argument("--insert-batch", type=int, default=4000)
    ap.add_argument("--varlen-seeds", type=int, default=1)
    # phases b and c: the repo's north-star graph at its on-chip default
    ap.add_argument("--persons", type=int, default=1_000_000)
    ap.add_argument("--degree", type=int, default=30)
    ap.add_argument("--pagerank-iters", type=int, default=5)
    args = ap.parse_args(argv)

    import jax

    from nebula_tpu import native
    from nebula_tpu.tpu.device import device_identity, enable_compile_cache

    device = device_identity()
    if device["platform"] != "tpu" and not args.rehearse:
        print(f"chip_smoke: jax found platform {device['platform']!r} "
              f"({device['kind']}), not a TPU — nothing to prove here "
              f"(--rehearse runs the phases anyway)", file=sys.stderr)
        return 2
    say(f"device: {json.dumps(device)}; jax {jax.__version__}")

    from nebula_tpu.utils.stats import stats
    base = stats().snapshot()      # the counters are process-wide
    sm = Smoke(args, device)
    cache_dir = enable_compile_cache()

    def n_entries():
        with contextlib.suppress(OSError):
            return len(os.listdir(cache_dir))
        return 0
    entries0 = n_entries()
    say(f"compile cache: {cache_dir} ("
        f"{'JAX_COMPILATION_CACHE_DIR' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'checkout default'}"
        f"), {entries0} entries before")

    # built from what git would commit: the native library is compiled
    # from native/nebula_native.cc here, never taken from disk
    sm.check("native/libnebula_native.so builds from source",
             native.build())
    sm.check("the native path is in use", native.available())

    if args.chips == 4:
        sm.phase("c", lambda: phase_c(sm))
        want = ["c"]
    else:
        sm.phase("a", lambda: phase_a(sm))
        sm.phase("b", lambda: phase_b(sm))
        want = ["a", "b"]

    fb = counted_fallbacks(base)
    sm.check("no execute-time device->host fallback was counted",
             not fb, json.dumps(fb))
    runs = int(stats().snapshot().get("tpu_kernel_runs", 0)
               - base.get("tpu_kernel_runs", 0))
    sm.check("tpu_kernel_runs covers every device statement",
             runs >= sm.device_statements,
             f"{runs} runs, {sm.device_statements} device statements")
    sm.check("every phase ran", sm.phases_run == want, str(sm.phases_run))
    say(f"compile cache: {n_entries()} entries after ({entries0} "
        f"before); persistent-cache hits {sm.watch.cache_hits}, misses "
        f"{sm.watch.cache_misses}; {sm.watch.compiles} backend compiles, "
        f"{sm.watch.compile_s:.1f}s")
    say(f"{sm.checks} checks, {len(sm.failures)} failed"
        + (": " + "; ".join(sm.failures) if sm.failures else ""))

    ok = not sm.failures and device["platform"] == "tpu" \
        and device["count"] == args.chips
    if device["count"] != args.chips:
        say(f"device count {device['count']} != --chips {args.chips}")
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
