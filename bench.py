#!/usr/bin/env python3
"""Benchmark harness: BASELINE.md configs 1-6 on one chip.

Prints ONE compact (≤500 byte) JSON headline as the LAST stdout line:
  {"metric": ..., "value": N, "unit": "edges/s", "vs_baseline": R,
   "platform": ..., ...}
and writes the full per-config detail to BENCH_DETAIL.json (the driver
tails stdout into a small buffer — VERDICT r3 item 2).

Runs in ONE process, the one that holds the chip: no probe child, no
re-exec, no fallback sizes.  It fails when the platform is not `tpu`
(unless the caller set JAX_PLATFORMS=cpu for a rehearsal) and any
phase that raises ends the run with a non-zero exit.

value        = device E2E traversed-edges/s on the north-star config
               (SF100-proxy 3-hop GO, wall time including frontier
               upload, kernel, result fetch AND row materialization).
vs_baseline  = that number over the CPU baseline's edges/s on the SAME
               query.  The CPU baseline for the north-star config is a
               fully vectorized numpy CSR walk (host_csr_traverse) —
               far stronger than a row-at-a-time engine; the small
               configs also report this framework's own query-engine
               wall time with the device plane off vs on (identical
               result rows asserted).

Per BASELINE.md row 6, the SF100 dataset itself is unreachable offline;
the north-star config is a stated scaled proxy (default 1M persons /
~30M edges, LDBC-SNB-shaped degree tail with Zipf supernodes) —
override with NEBULA_BENCH_PERSONS / NEBULA_BENCH_DEGREE.

Kernel-only numbers are in detail (VERDICT r1: the headline must be
end-to-end, not kernel-time).
"""
from __future__ import annotations

import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

REPEATS = int(os.environ.get("NEBULA_BENCH_REPEATS", 3))


def _mark(msg):
    """Progress marker on stderr (the JSON contract owns stdout) — a
    mid-bench stall must be attributable to a phase."""
    print(f"[bench +{time.perf_counter() - _T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


_T0 = time.perf_counter()

def _median(xs):
    return statistics.median(xs)


def _gc_settle():
    """Collect then freeze the live object graph (graphs, pinned
    snapshots, the jax runtime) out of the collector's scan set.
    Periodic gen-2 collections over jax's module graph stalled queries
    by ~250 ms — a bimodal 60/290 ms p50 on an otherwise idle host.
    Freezing is cumulative and cheap; fresh garbage is still collected."""
    import gc
    gc.collect()
    gc.freeze()


def bench_engine_config(name, store, query, seeds_note, rt, space="snb",
                        numpy_fn=None, canon=None, repeats=None):
    """Engine-E2E wall time, device plane OFF vs ON, identical rows.

    `numpy_fn` (VERDICT r2 item 2) is the HONEST CPU comparator: a
    vectorized numpy CSR/columnar implementation of the same query.  It
    is timed like the engine runs, its result is content-checked against
    the engine rows via `canon(rows) == numpy_fn()`, and the per-config
    speedup is reported against BOTH the framework's own host engine
    (`speedup_e2e`) AND numpy (`speedup_vs_numpy`) — the row-at-a-time
    Python engine is never quoted as "CPU" in a headline."""
    from nebula_tpu.exec.engine import QueryEngine

    n_rep = REPEATS if repeats is None else repeats
    out = {}
    rows_by_mode = {}
    for mode, runtime in (("cpu", None), ("tpu", rt)):
        eng = QueryEngine(store, tpu_runtime=runtime)
        s = eng.new_session()
        eng.execute(s, f"USE {space}")
        rs = eng.execute(s, query)          # warmup (compile + pin)
        assert rs.error is None, f"{name}: {rs.error}"
        _gc_settle()
        lat = []
        for _ in range(n_rep):
            t0 = time.perf_counter()
            rs = eng.execute(s, query)
            lat.append(time.perf_counter() - t0)
        rows_by_mode[mode] = sorted(map(repr, rs.data.rows))
        st = eng.qctx.last_tpu_stats
        edges = st.edges_traversed() if st is not None else None
        out[mode] = {"p50_ms": round(_median(lat) * 1e3, 2),
                     "rows": len(rs.data.rows)}
        if mode == "tpu" and st is not None:
            out["edges_per_run"] = edges
            out["tpu_kernel_ms"] = round(st.device_s * 1e3, 2)
            out["tpu_e2e_eps"] = round(edges / _median(lat), 1)
            out["cpu_eps"] = round(edges / (out["cpu"]["p50_ms"] / 1e3), 1)
            out["speedup_e2e"] = round(out["cpu"]["p50_ms"]
                                       / out["tpu"]["p50_ms"], 3)
        if mode == "tpu" and numpy_fn is not None:
            nlat = []
            for _ in range(REPEATS):
                t0 = time.perf_counter()
                nres = numpy_fn()
                nlat.append(time.perf_counter() - t0)
            out["numpy_p50_ms"] = round(_median(nlat) * 1e3, 2)
            out["speedup_vs_numpy"] = round(_median(nlat) / _median(lat),
                                            3)
            if canon is not None:
                import numpy as _np
                want, got = canon(rs.data), nres
                assert len(want) == len(got), (len(want), len(got))
                assert all(_np.array_equal(_np.asarray(a), _np.asarray(b))
                           for a, b in zip(want, got)), \
                    f"{name}: numpy comparator rows differ"
                out["numpy_rows_match"] = True
    assert rows_by_mode["cpu"] == rows_by_mode["tpu"], \
        f"{name}: device rows differ from host rows"
    out["identical_rows"] = True
    return out


def main():
    # ONE process holds the chip for the whole run: no probe child, no
    # re-exec.  A platform other than `tpu` fails here unless the
    # caller set JAX_PLATFORMS=cpu on purpose (a rehearsal).
    from nebula_tpu.tpu.device import enable_compile_cache, require_tpu
    device = require_tpu("bench.py")
    _mark(f"device: {device}; compile cache at {enable_compile_cache()}")
    # supernode degree-split (SURVEY §7 hard-part #4): spreads each
    # hub's adjacency across the mesh at pin time — smaller per-hop
    # padded budgets on the Zipf tail, and the owner chip no longer
    # serializes a supernode's expansion.  Override/disable with
    # NEBULA_BENCH_DEGREE_SPLIT=<threshold|0>.
    split_thr = int(os.environ.get("NEBULA_BENCH_DEGREE_SPLIT", 2048))
    if split_thr > 0:
        from nebula_tpu.utils.config import get_config
        get_config().set_dynamic("tpu_degree_split_threshold", split_thr)
    n_persons = int(os.environ.get("NEBULA_BENCH_PERSONS", 1_000_000))
    degree = int(os.environ.get("NEBULA_BENCH_DEGREE", 30))
    small_n = int(os.environ.get("NEBULA_BENCH_SMALL_PERSONS", 50_000))
    parts = int(os.environ.get("NEBULA_BENCH_PARTS", 8))
    n_seeds = int(os.environ.get("NEBULA_BENCH_SEEDS", 16))

    import numpy as np

    from nebula_tpu.bench.datagen import (SnapshotStore, host_bfs,
                                          host_csr_traverse,
                                          host_match_agg, host_trail_paths,
                                          make_social_arrays,
                                          make_social_graph, pick_seeds,
                                          snapshot_from_arrays)
    from nebula_tpu.graphstore.csr import build_snapshot
    from nebula_tpu.core import expr as E
    from nebula_tpu.tpu.runtime import TpuRuntime

    rt = TpuRuntime()
    platform = device["platform"]
    configs = {}

    # ---- configs 1 + 2: engine E2E on the dict store (identical rows) ----
    # The small graph is built THROUGH the bulk import path (VERDICT r3
    # item 6): LDBC-SNB-shaped '|'-delimited CSVs → tools/ldbc_import
    # (knows.csv is all-numeric, so the edge leg exercises the native
    # csv_ingest parser; person.csv has the string name column and takes
    # the csv.reader leg).
    _mark("writing SNB-shaped CSVs (small graph)")
    import tempfile
    from nebula_tpu.bench.datagen import write_snb_csvs
    from nebula_tpu.graphstore.store import GraphStore
    from nebula_tpu.tools import ldbc_import as ldbc
    csv_dir = tempfile.mkdtemp(prefix="nebula_bench_snb_")
    ppath, kpath, lpath, n_pv, n_ke, n_le = write_snb_csvs(
        csv_dir, small_n, degree, seed=7)
    _mark(f"importing {n_pv} persons + {n_ke} knows + {n_le} likes "
          f"via ldbc_import")
    t0 = time.perf_counter()
    store = GraphStore()
    store.create_space("snb", partition_num=parts, vid_type="INT64")
    got_v = ldbc.import_vertices(
        store, "snb", f"Person:{ppath}:id,age:int,name:string", "|",
        vid_is_int=True, header=True)
    got_e = ldbc.import_edges(
        store, "snb", f"KNOWS:{kpath}:src,dst,w:int,f:float", "|",
        vid_is_int=True, header=True)
    got_l = ldbc.import_edges(
        store, "snb", f"LIKES:{lpath}:src,dst,w:int,f:float", "|",
        vid_is_int=True, header=True)
    small_build_s = time.perf_counter() - t0
    assert got_v == n_pv and got_e == n_ke and got_l == n_le, \
        (got_v, n_pv, got_e, n_ke, got_l, n_le)
    import_info = {"csv_dir": csv_dir, "person_rows": got_v,
                   "knows_rows": got_e, "likes_rows": got_l,
                   "import_s": round(small_build_s, 2),
                   "native_lib": __import__(
                       "nebula_tpu.native", fromlist=["get_lib"]
                   ).get_lib() is not None}
    import shutil
    shutil.rmtree(csv_dir, ignore_errors=True)
    seeds = pick_seeds(store, "snb", n_seeds, min_degree=2)
    seed_list = ", ".join(str(s) for s in seeds)

    # the honest CPU comparator for configs 1-4 (VERDICT r2 item 2): a
    # numpy CSR/columnar implementation of each query over the SAME data
    _mark("building numpy comparator snapshot (small graph)")
    snap_small = build_snapshot(store, "snb")
    sd_small = store.space("snb")
    dense_seeds = [sd_small.dense_id(v) for v in seeds]
    d2v_small = np.asarray(snap_small.dense_to_vid, dtype=np.int64)

    def np_cfg1():
        _, _, nxt, _w = host_csr_traverse(snap_small, dense_seeds, 2,
                                          materialize=True)
        return (np.sort(d2v_small[nxt]),)

    def canon_cfg1(ds):
        return (np.sort(np.asarray(ds.column("d"), np.int64)),)

    def np_cfg2():
        _, _, nxt, w = host_csr_traverse(snap_small, dense_seeds, 3,
                                         w_gt=50, materialize=True)
        d = d2v_small[nxt]
        o = np.lexsort((w, d))
        return (d[o], w[o].astype(np.int64))

    def canon_cfg2(ds):
        d = np.asarray(ds.column("d"), np.int64)
        w = np.asarray(ds.column("w"), np.int64)
        o = np.lexsort((w, d))
        return (d[o], w[o])

    _mark("config 1: engine e2e GO 2 STEPS")
    configs["1_sf1_go2"] = bench_engine_config(
        "cfg1", store,
        f"GO 2 STEPS FROM {seed_list} OVER KNOWS YIELD dst(edge) AS d",
        seeds, rt, numpy_fn=np_cfg1, canon=canon_cfg1)

    # Headline configs run EARLY (right after the config-1 sanity pass).
    rt.unpin("snb")   # headline runs with ONLY the ns snapshot resident
    # (same HBM environment as every prior round's record; configs
    # 2/2b/3 re-pin snb automatically when they run afterwards)
    # ---- north-star-scale array graph (configs 5 + 6) ----
    _mark("building north-star array graph")
    t0 = time.perf_counter()
    arrs = make_social_arrays(n_persons, degree, seed=7)
    snap = snapshot_from_arrays(arrs, parts=parts, space="ns")
    snap.space = "ns"
    big_build_s = time.perf_counter() - t0
    sstore = SnapshotStore(snap)
    deg_out = np.diff(snap.block("KNOWS", "out").indptr, axis=1)
    skew = {"max_degree": int(deg_out.max()),
            "per_part_edges": snap.block("KNOWS", "out")
                                  .indptr[:, -1].tolist()}
    _mark("pinning north-star snapshot to device")
    rt.pin_prebuilt(snap)
    big_seeds = np.unique(arrs["src"][:4 * n_seeds])[:n_seeds].tolist()

    # config 6: the north-star — 3-hop GO, E2E with final-row output
    yields = [(E.FunctionCall("dst", [E.EdgeExpr()]), "d"),
              (E.EdgeProp("KNOWS", "w"), "w")]
    _mark("config 6: warmup traverse (compile + escalation)")
    rows, st = rt.traverse(sstore, "ns", big_seeds, ["KNOWS"], "out", 3,
                           yields=yields)   # warmup + escalation settle
    _gc_settle()
    _mark("config 6: timed repeats (device/numpy interleaved A/B)")
    # VERDICT r4 weak #3: the shared-VM numpy comparator swings 2-5x
    # run-to-run, so A/B runs INTERLEAVE and both sides report medians
    # plus dispersion — vs_baseline is median-over-median with the
    # spread stated next to it.
    lat, klat, cpu_lat = [], [], []
    cpu_total = cpu_kept = 0
    cpu_dst = cpu_w = None
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        rows, st = rt.traverse(sstore, "ns", big_seeds, ["KNOWS"], "out",
                               3, yields=yields)
        lat.append(time.perf_counter() - t0)
        klat.append(st.device_s)
        t0 = time.perf_counter()
        cpu_total, cpu_kept, cpu_dst, cpu_w = host_csr_traverse(
            snap, big_seeds, 3, materialize=True)
        cpu_lat.append(time.perf_counter() - t0)
    edges = st.edges_traversed()
    cfg6_st = st               # pinned for the regression block below
    cpu_s = _median(cpu_lat)
    assert cpu_total == edges, (cpu_total, edges)
    assert cpu_kept == len(rows)
    # content equality, not just counts: device rows == baseline arrays
    # (rows is a lazy ColumnarDataSet — compare columns directly)
    dev_d = np.asarray(rows.column_array("d"), np.int64)
    dev_w = np.asarray(rows.column_array("w"), np.int64)
    order_dev = np.lexsort((dev_w, dev_d))
    order_cpu = np.lexsort((cpu_w, cpu_dst))
    assert (dev_d[order_dev] == cpu_dst[order_cpu]).all()
    assert (dev_w[order_dev] == cpu_w[order_cpu]).all()
    tpu_e2e_eps = edges / _median(lat)
    tpu_kernel_eps = edges / _median(klat)
    cpu_eps = cpu_total / cpu_s
    # client boundary (VERDICT r4 item 2): the columnar result ships
    # through the REAL rpc frame (raw column buffers out-of-band of the
    # JSON) and decodes back to numpy on the client — this is everything
    # a wire client pays beyond the engine E2E.  Content re-checked.
    _mark("config 6: columnar client wire boundary")
    from nebula_tpu.cluster.rpc import RpcClient, RpcServer
    from nebula_tpu.core import wire as _wire
    _srv = RpcServer()
    _srv.register("result", lambda p: {"data": _wire.to_wire(rows)})
    _srv.start()
    _cl = RpcClient(_srv.host, _srv.port, timeout=120.0)
    _cl.call("result")                     # connection + page-in warmup
    client_lat = []
    for _ in range(3):
        t0 = time.perf_counter()
        got = _wire.from_wire(_cl.call("result")["data"])
        client_lat.append(time.perf_counter() - t0)
    # deterministic work counters (ISSUE 1 / VERDICT weak #8): the
    # noise-immune regression signal.  Two probe runs of the north-star
    # traverse + one client wire round-trip must agree BYTE-FOR-BYTE —
    # work counts are stable across noisy VMs even when timings are not.
    # Probes run post-warmup (converged buckets), so dispatch counts and
    # frontier sizes are reproducible; diff these across rounds instead
    # of eps when the VM is suspect (docs/OBSERVABILITY.md).
    _mark("config 6: deterministic work-counter probes")
    from nebula_tpu.utils.stats import WorkCounters, use_work

    def _work_probe():
        wc = WorkCounters()
        with use_work(wc):
            rt.traverse(sstore, "ns", big_seeds, ["KNOWS"], "out", 3,
                        yields=yields)
            _wire.from_wire(_cl.call("result")["data"])
        return wc.as_dict()

    work1, work2 = _work_probe(), _work_probe()
    assert json.dumps(work1) == json.dumps(work2), \
        f"work counters not deterministic: {work1} != {work2}"
    _cl.close()
    _srv.stop()
    cg = np.asarray(got.column_array("d"), np.int64)
    assert cg.shape[0] == len(rows) and \
        np.array_equal(np.sort(cg), np.sort(dev_d)), \
        "client-decoded columns diverge"
    client_s = _median(client_lat)
    tpu_client_eps = edges / (_median(lat) + client_s)
    # row boundary cost, reported separately: what a consumer would pay
    # to build per-row Python lists instead of consuming columns
    t0 = time.perf_counter()
    _ = rows.rows
    rows_ms = (time.perf_counter() - t0) * 1e3
    configs["6_north_star_go3"] = {
        "edges_per_run": edges, "result_rows": len(rows),
        "p50_ms": round(_median(lat) * 1e3, 2),
        "kernel_p50_ms": round(_median(klat) * 1e3, 2),
        "mat_ms": round(st.mat_s * 1e3, 2),
        "rows_ms": round(rows_ms, 2),
        "client_wire_ms": round(client_s * 1e3, 2),
        "fetch_ms": round(st.fetch_s * 1e3, 2),
        "tpu_e2e_eps": round(tpu_e2e_eps, 1),
        "tpu_client_eps": round(tpu_client_eps, 1),
        "client_vs_numpy": round(tpu_client_eps / cpu_eps, 3),
        "tpu_kernel_eps": round(tpu_kernel_eps, 1),
        "cpu_numpy_eps": round(cpu_eps, 1),
        "cpu_p50_ms": round(cpu_s * 1e3, 2),
        "cpu_ms_spread": [round(min(cpu_lat) * 1e3, 1),
                          round(max(cpu_lat) * 1e3, 1)],
        "tpu_ms_spread": [round(min(lat) * 1e3, 1),
                          round(max(lat) * 1e3, 1)],
        "identical_rows": True,
        "buckets": {"EB": st.e_cap},
        "work_counters": work1,
        "work_counters_identical": True,
    }

    # config 5: shortest-path BFS device plane, content-checked against
    # a numpy level-synchronous BFS (VERDICT r3 weak #5: oracle)
    _mark("config 5: BFS")
    bfs_src = big_seeds[:1]
    dist, stb = rt.bfs(sstore, "ns", bfs_src, ["KNOWS"], "out", 5)
    _gc_settle()
    lat = []
    for _ in range(3):
        t0 = time.perf_counter()
        dist, stb = rt.bfs(sstore, "ns", bfs_src, ["KNOWS"], "out", 5)
        lat.append(time.perf_counter() - t0)
    _mark("config 5: numpy BFS oracle")
    sd_ns = sstore.space("ns")
    t0 = time.perf_counter()
    np_dist = host_bfs(snap, [sd_ns.dense_id(v) for v in bfs_src], 5)
    np_bfs_s = time.perf_counter() - t0
    # device dist is (P, Vmax) part-major; dense id v lives at
    # [v % P, v // P]
    dev_dist = np.asarray(dist, np.int32)
    nv = np_dist.shape[0]
    vv = np.arange(nv)
    assert np.array_equal(dev_dist[vv % parts, vv // parts], np_dist), \
        "config 5: device BFS distances differ from numpy BFS"
    configs["5_shortest_path_bfs"] = {
        "reached": int((np_dist >= 0).sum()),
        "edges_per_run": stb.edges_traversed(),
        "p50_ms": round(_median(lat) * 1e3, 2),
        "kernel_ms": round(stb.device_s * 1e3, 2),
        "numpy_p50_ms": round(np_bfs_s * 1e3, 2),
        "distances_match_numpy": True,
    }
    # record the headline configs' device footprint, then release the
    # big snapshot so the small configs don't share HBM with it (and a
    # tpu_hbm_limit_bytes budget can't silently push them to host)
    ns_hbm_bytes = rt.hbm_bytes()
    rt.unpin("ns")

    _mark("config 2: engine e2e GO 3 STEPS filtered")
    configs["2_sf30_go3_filtered"] = bench_engine_config(
        "cfg2", store,
        f"GO 3 STEPS FROM {seed_list} OVER KNOWS WHERE KNOWS.w > 50 "
        f"YIELD dst(edge) AS d, KNOWS.w AS w",
        seeds, rt, numpy_fn=np_cfg2, canon=canon_cfg2)

    # config 2b (BASELINE row 2's OVER * shape): multi-edge-type
    # expansion — two CSR blocks per hop on device (the per-edge-type
    # block axis).  Unfiltered: the fused predicate mask is single-etype
    # by design (per-block prop columns), so the filtered leg above
    # keeps OVER KNOWS.
    def np_cfg2b():
        _, _, nxt, _w = host_csr_traverse(snap_small, dense_seeds, 3,
                                          materialize=True,
                                          etypes=("KNOWS", "LIKES"))
        return (np.sort(d2v_small[nxt]),)

    _mark("config 2b: engine e2e GO 3 STEPS OVER *")
    configs["2b_go3_over_all"] = bench_engine_config(
        "cfg2b", store,
        f"GO 3 STEPS FROM {seed_list} OVER * YIELD dst(edge) AS d",
        seeds, rt, numpy_fn=np_cfg2b, canon=canon_cfg1)

    # config 3 (BASELINE: IC5/IC9-shaped): fixed-length MATCH pattern +
    # aggregate — Traverse + Aggregate executor composition, device
    # frames vs host DFS with identical grouped rows.
    _mark("config 3: engine e2e IC-shaped MATCH + aggregate")
    ic_seeds = ", ".join(str(s) for s in seeds[:4])
    dense_ic = dense_seeds[:4]

    def np_cfg3():
        u, c = host_match_agg(snap_small, dense_ic, 30)
        return (d2v_small[u], c.astype(np.int64))

    def canon_cfg3(ds):
        v = np.asarray(ds.column("v"), np.int64)
        c = np.asarray(ds.column("c"), np.int64)
        o = np.argsort(v)
        return (v[o], c[o])

    configs["3_ic_match_agg"] = bench_engine_config(
        "cfg3", store,
        f"MATCH (p:Person)-[:KNOWS]->(f)-[:KNOWS]->(ff:Person) "
        f"WHERE id(p) IN [{ic_seeds}] AND ff.Person.age > 30 "
        f"RETURN id(ff) AS v, count(*) AS c",
        seeds, rt, numpy_fn=np_cfg3, canon=canon_cfg3)
    rt.unpin("snb")

    # config 4 (BASELINE: Twitter-2010-shaped): variable-length *1..4
    # MATCH — path explosion + trail dedup; device layered-frame capture
    # + host assembly vs pure host DFS.  VERDICT r5 weak #4: the old
    # 8k-person/8-seed slice traversed 9,949 edges per run; it now runs
    # at two scales:
    #   4_twitter_var_len  — denser A/B slice (~200k traversed edges,
    #       ~400k trails): device vs HOST ENGINE vs numpy, identical
    #       rows on all three.
    #   4b_twitter_stress  — the ≥1M-traversed-edges explosion slice
    #       (~2.7M trails): device vs the numpy trail-join oracle,
    #       identical rows.  The HOST ROW PLANE sits this one out, and
    #       that exclusion IS the stated ceiling: ~2.7M emitted rows
    #       × ~512B of per-path Python lists ≈ 1.4 GB intermediates
    #       (over the 1 GiB default query_memory_limit_bytes) and one
    #       get_neighbors call per expansion ≈ 10+ min/run on the bench
    #       VM — the row-at-a-time plane cannot execute this config
    #       inside budget, which is exactly the cliff the columnar
    #       plane exists to remove.
    _mark("building twitter-proxy graph (config 4 A/B slice)")
    tw_n = int(os.environ.get("NEBULA_BENCH_TW_PERSONS", 30_000))
    tw_deg = int(os.environ.get("NEBULA_BENCH_TW_DEGREE", 12))
    tw_nseeds = int(os.environ.get("NEBULA_BENCH_TW_SEEDS", 16))
    tw = make_social_graph(n_persons=tw_n, avg_degree=tw_deg, parts=parts,
                           seed=11, space="tw")
    tw_seeds = pick_seeds(tw, "tw", tw_nseeds, min_degree=3)
    tw_list = ", ".join(str(s) for s in tw_seeds)
    snap_tw = build_snapshot(tw, "tw")
    sd_tw = tw.space("tw")
    dense_tw = [sd_tw.dense_id(v) for v in tw_seeds]
    n_paths = host_trail_paths(snap_tw, dense_tw, 4)

    def np_cfg4():
        return (np.int64(host_trail_paths(snap_tw, dense_tw, 4)),)

    def canon_cfg4(ds):
        return (np.int64(ds.rows[0][0]),)

    _mark(f"config 4: engine e2e MATCH *1..4 ({n_paths} trails)")
    configs["4_twitter_var_len"] = bench_engine_config(
        "cfg4", tw,
        f"MATCH (a:Person)-[e:KNOWS*1..4]->(b) WHERE id(a) IN [{tw_list}] "
        f"RETURN count(*) AS paths",
        tw_seeds, rt, space="tw", numpy_fn=np_cfg4, canon=canon_cfg4)
    configs["4_twitter_var_len"].update({
        "persons": tw_n, "avg_degree": tw_deg, "seeds": tw_nseeds,
        "trail_paths": int(n_paths)})
    rt.unpin("tw")

    # ---- config 4b: the ≥1M-edge explosion slice (device + numpy) ----
    _mark("building twitter-proxy graph (config 4b stress slice)")
    twb_n = int(os.environ.get("NEBULA_BENCH_TWB_PERSONS", 150_000))
    twb_nseeds = int(os.environ.get("NEBULA_BENCH_TWB_SEEDS", 1_792))
    twb = make_social_graph(n_persons=twb_n, avg_degree=6, parts=parts,
                            seed=11, space="twb")
    twb_seeds = pick_seeds(twb, "twb", twb_nseeds, min_degree=3)
    snap_twb = build_snapshot(twb, "twb")
    sd_twb = twb.space("twb")
    dense_twb = [sd_twb.dense_id(v) for v in twb_seeds]
    t0 = time.perf_counter()
    twb_paths = host_trail_paths(snap_twb, dense_twb, 4)
    twb_np_s = time.perf_counter() - t0
    _mark(f"config 4b: device MATCH *1..4 ({twb_paths} trails)")
    from nebula_tpu.exec.engine import QueryEngine as _QE
    _e4b = _QE(twb, tpu_runtime=rt)
    _s4b = _e4b.new_session()
    _e4b.execute(_s4b, "USE twb")
    twb_q = (f"MATCH (a:Person)-[e:KNOWS*1..4]->(b) WHERE id(a) IN "
             f"[{', '.join(str(s) for s in twb_seeds)}] "
             f"RETURN count(*) AS paths")
    r4b = _e4b.execute(_s4b, twb_q)          # warmup + correctness
    assert r4b.error is None, r4b.error
    assert int(r4b.data.rows[0][0]) == int(twb_paths), \
        "config 4b: device trail count diverges from the numpy oracle"
    _gc_settle()
    lat4b = []
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        r4b = _e4b.execute(_s4b, twb_q)
        lat4b.append(time.perf_counter() - t0)
    st4b = _e4b.qctx.last_tpu_stats
    edges4b = st4b.edges_traversed() if st4b is not None else 0
    configs["4b_twitter_stress"] = {
        "persons": twb_n, "avg_degree": 6, "seeds": twb_nseeds,
        "trail_paths": int(twb_paths),
        "edges_per_run": int(edges4b),
        "device_p50_ms": round(_median(lat4b) * 1e3, 2),
        "numpy_p50_ms": round(twb_np_s * 1e3, 2),
        "speedup_vs_numpy": round(twb_np_s / _median(lat4b), 3),
        "identical_rows": True,
        "snapshot_bytes": snap_twb.hbm_bytes(),
        "host_row_plane": "excluded — RAM/time ceiling: ~2.7M rows x "
                          "~512B path lists ≈ 1.4GB > 1GiB default "
                          "query_memory_limit_bytes, and one "
                          "get_neighbors call per expansion ≈ 10+ "
                          "min/run; the columnar plane runs it in "
                          "seconds (this exclusion is the config's "
                          "point)",
    }
    rt.unpin("twb")

    # ---- configs ic5 + ic9 (VERDICT r4 item 6): the published LDBC
    # interactive query text verbatim (tie-breaks adapted to title/id
    # where the official text orders by a column our schema spells
    # differently) over the SNB-interactive slice, numpy oracles ----
    from nebula_tpu.bench.datagen import (ic5_numpy, ic9_numpy,
                                          make_snb_interactive)
    _mark("building SNB interactive slice (ic5/ic9)")
    # VERDICT r5 weak #3 / ISSUE 4: the IC slice runs at 6,000 persons
    # — the fused columnar pipeline is expected to WIN here
    # (acceptance: device ≥2× host), so toy scale no longer hides the
    # tail cost
    ic_n = int(os.environ.get("NEBULA_BENCH_IC_PERSONS", 6_000))
    ic_store, ic_arr = make_snb_interactive(ic_n, parts=parts)
    ic_root, ic_min, ic_max = 5, 17_000, 19_000
    ic5_q = (
        f"MATCH (person:Person)-[:KNOWS*1..2]-(friend:Person) "
        f"WHERE id(person) == {ic_root} AND id(friend) != {ic_root} "
        f"WITH DISTINCT friend "
        f"MATCH (friend)<-[membership:HAS_MEMBER]-(forum:Forum) "
        f"WHERE membership.joinDate > {ic_min} "
        f"WITH DISTINCT friend, forum "
        f"OPTIONAL MATCH (friend)<-[:HAS_CREATOR]-(post:Post)"
        f"<-[:CONTAINER_OF]-(forum) "
        f"WITH forum, count(post) AS postCount "
        f"RETURN forum.Forum.title AS forumName, postCount "
        f"ORDER BY postCount DESC, forumName ASC LIMIT 20")
    ic9_q = (
        f"MATCH (root:Person)-[:KNOWS*1..2]-(friend:Person) "
        f"WHERE id(root) == {ic_root} AND id(friend) != {ic_root} "
        f"WITH DISTINCT friend "
        f"MATCH (friend)<-[:HAS_CREATOR]-(message) "
        f"WHERE message.creationDate < {ic_max} "
        f"RETURN id(friend) AS fid, id(message) AS mid, "
        f"message.creationDate AS d ORDER BY d DESC, mid ASC LIMIT 20")

    def _run_ic(name, q, oracle_rows):
        from nebula_tpu.exec.engine import QueryEngine
        for tag, tpu_rt in (("host", None), ("device", rt)):
            e = QueryEngine(ic_store, tpu_runtime=tpu_rt)
            ss = e.new_session()
            e.execute(ss, "USE ic")
            r = e.execute(ss, q)       # warmup + correctness
            assert r.error is None, f"{name} {tag}: {r.error}"
            got = [tuple(row) for row in r.data.rows]
            assert got == oracle_rows, \
                f"{name} {tag} rows diverge from the numpy oracle"
            lat = []
            for _ in range(3):
                t0 = time.perf_counter()
                r = e.execute(ss, q)
                lat.append(time.perf_counter() - t0)
            yield tag, _median(lat)

    _mark("config ic5")
    want5 = [tuple(t) for t in ic5_numpy(ic_arr, ic_root, ic_min)]
    ic5_ms = dict(_run_ic("ic5", ic5_q, want5))
    _mark("config ic9")
    want9 = [tuple(t) for t in ic9_numpy(ic_arr, ic_root, ic_max)]
    ic9_ms = dict(_run_ic("ic9", ic9_q, want9))
    configs["ic5"] = {"persons": ic_n, "rows": len(want5),
                      "host_p50_ms": round(ic5_ms["host"] * 1e3, 2),
                      "device_p50_ms": round(ic5_ms["device"] * 1e3, 2),
                      "device_vs_host": round(ic5_ms["host"]
                                              / ic5_ms["device"], 3),
                      "oracle": "numpy ic5_numpy, rows asserted equal "
                                "on BOTH planes",
                      "identical_rows": True}
    configs["ic9"] = {"persons": ic_n, "rows": len(want9),
                      "host_p50_ms": round(ic9_ms["host"] * 1e3, 2),
                      "device_p50_ms": round(ic9_ms["device"] * 1e3, 2),
                      "device_vs_host": round(ic9_ms["host"]
                                              / ic9_ms["device"], 3),
                      "oracle": "numpy ic9_numpy, rows asserted equal "
                                "on BOTH planes",
                      "identical_rows": True}

    # ---- config write (VERDICT r4 weak #8): INSERT-heavy through the
    # cluster write path — raft consensus per part + TOSS chain edge
    # writes — with a read-after-write count oracle ----
    _mark("config write: raft+TOSS insert throughput")
    import tempfile
    from nebula_tpu.cluster.launcher import LocalCluster
    wn = int(os.environ.get("NEBULA_BENCH_WRITE_PERSONS", 4_000))
    wdeg = 4
    wtmp = tempfile.mkdtemp(prefix="nebula_bench_write_")
    wc = LocalCluster(n_meta=1, n_storage=2, n_graph=1, data_dir=wtmp)
    try:
        wcl = wc.client()
        assert wcl.execute(
            "CREATE SPACE wr(partition_num=8, vid_type=INT64)").error \
            is None
        wc.reconcile_storage()
        for q in ("USE wr", "CREATE TAG Person(age int)",
                  "CREATE EDGE KNOWS(w int)"):
            assert wcl.execute(q).error is None, q
        rng_w = np.random.default_rng(23)
        wsrc = rng_w.integers(0, wn, wn * wdeg)
        wdst = rng_w.integers(0, wn, wn * wdeg)
        keepw = wsrc != wdst
        wsrc, wdst = wsrc[keepw], wdst[keepw]
        t0 = time.perf_counter()
        B = 200
        for lo in range(0, wn, B):
            vals = ", ".join(f"{v}:({v % 80})"
                             for v in range(lo, min(lo + B, wn)))
            r = wcl.execute(f"INSERT VERTEX Person(age) VALUES {vals}")
            assert r.error is None, r.error
        v_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        for lo in range(0, wsrc.size, B):
            vals = ", ".join(
                f"{s}->{d}:({int(s + d) % 100})"
                for s, d in zip(wsrc[lo:lo + B].tolist(),
                                wdst[lo:lo + B].tolist()))
            r = wcl.execute(f"INSERT EDGE KNOWS(w) VALUES {vals}")
            assert r.error is None, r.error
        e_s = time.perf_counter() - t0
        # read-after-write oracle: 1-hop GO from a seed set must match
        # the numpy adjacency built from the same arrays (last write
        # wins on duplicate (src, dst) — rank 0 upsert)
        adj = {}
        for s, d in zip(wsrc.tolist(), wdst.tolist()):
            adj.setdefault(s, set()).add(d)
        wseeds = [s for s in sorted(adj)[:8]]
        r = wcl.execute(f"GO FROM {', '.join(map(str, wseeds))} "
                        f"OVER KNOWS YIELD src(edge) AS s, dst(edge) AS d")
        assert r.error is None, r.error
        got_pairs = sorted((row[0], row[1]) for row in r.data.rows)
        want_pairs = sorted((s, d) for s in wseeds for d in adj[s])
        assert got_pairs == want_pairs, "write config read-back diverges"
        configs["write_raft_toss"] = {
            "vertices": wn, "edges": int(wsrc.size),
            "vertex_inserts_per_s": round(wn / v_s, 1),
            "edge_inserts_per_s": round(wsrc.size / e_s, 1),
            # the BENCH headline for the write path (ISSUE 3): all
            # inserted rows over the whole cluster write-path wall time
            "insert_rows_per_sec": round((wn + int(wsrc.size))
                                         / (v_s + e_s), 1),
            "batch_rows": B, "readback_rows": len(got_pairs),
            "identical_rows": True,
        }
    finally:
        wc.stop()
    # group-commit A/B (ISSUE 3): per-command vs grouped proposals at
    # the same durability (sync WAL, 3-node raft) — the isolated
    # consensus-layer speedup behind insert_rows_per_sec
    _mark("config write: group-commit A/B (write_bench)")
    from nebula_tpu.tools.write_bench import run as _write_bench
    wb = _write_bench(entries=256, n_nodes=3)
    configs["write_raft_toss"].update({
        "percmd_proposals_per_s": wb["per_command_eps"],
        "grouped64_proposals_per_s": wb["grouped_64_eps"],
        "grouped_vs_percmd_64": wb["grouped_64_speedup"],
        "grouped_vs_percmd_512": wb["grouped_512_speedup"],
        "wal_batch_speedup": wb["wal_batch_speedup"],
    })

    # ---- concurrency block (ISSUE 9): ≥64 concurrent small GO/MATCH
    # statements against a live 3-replica cluster — p50/p95/p99 + QPS
    # with the queue-wait share of total latency, the baseline number
    # ROADMAP item 2 (admission control / device batching) must beat.
    _mark("config concurrency: 64-way small-query latency/QPS")
    import threading as _threading

    from nebula_tpu.utils.stats import stats as _cstats
    cn = int(os.environ.get("NEBULA_BENCH_CONC_PERSONS", 2_000))
    cdeg = 6
    cthreads = int(os.environ.get("NEBULA_BENCH_CONC_THREADS", 64))
    creps = int(os.environ.get("NEBULA_BENCH_CONC_REPS", 6))
    ctmp = tempfile.mkdtemp(prefix="nebula_bench_conc_")
    conc_cluster = LocalCluster(n_meta=1, n_storage=3, n_graph=1,
                                data_dir=ctmp, tpu_runtime=rt)
    try:
        ccl = conc_cluster.client()
        assert ccl.execute(
            "CREATE SPACE conc(partition_num=8, replica_factor=3, "
            "vid_type=INT64)").error is None
        conc_cluster.reconcile_storage()
        for q in ("USE conc", "CREATE TAG Person(age int)",
                  "CREATE EDGE KNOWS(w int)"):
            assert ccl.execute(q).error is None, q
        rng_c = np.random.default_rng(29)
        B = 400
        for lo in range(0, cn, B):
            vals = ", ".join(f"{v}:({v % 90})"
                             for v in range(lo, min(lo + B, cn)))
            r = ccl.execute(f"INSERT VERTEX Person(age) VALUES {vals}")
            assert r.error is None, r.error
        csrc = rng_c.integers(0, cn, cn * cdeg)
        cdst = rng_c.integers(0, cn, cn * cdeg)
        keepc = csrc != cdst
        csrc, cdst = csrc[keepc], cdst[keepc]
        for lo in range(0, csrc.size, B):
            vals = ", ".join(
                f"{s}->{d}:({int(s + d) % 100})"
                for s, d in zip(csrc[lo:lo + B].tolist(),
                                cdst[lo:lo + B].tolist()))
            r = ccl.execute(f"INSERT EDGE KNOWS(w) VALUES {vals}")
            assert r.error is None, r.error

        def _conc_stmt(i, j):
            # alternating small GO / MATCH — thousands of SMALL
            # statements is the admission-control workload shape, not
            # one big traversal
            seed = (i * 131 + j * 17) % cn
            if (i + j) % 2:
                return (f"MATCH (a:Person)-[e:KNOWS]->(b) "
                        f"WHERE id(a) == {seed} RETURN id(b)")
            return f"GO FROM {seed} OVER KNOWS YIELD dst(edge) AS d"

        warm = conc_cluster.client()
        warm.execute("USE conc")
        warm.execute(_conc_stmt(0, 0))
        warm.execute(_conc_stmt(0, 1))

        def _qwait_us(snap):
            # all kernels' dispatch-gate wait, µs (histogram sums)
            return sum(v for k, v in snap.items()
                       if k.startswith("tpu_dispatch_queue_us")
                       and k.endswith(".sum"))

        snap0 = _cstats().snapshot()
        conc_lats: list = []
        lat_lock = _threading.Lock()
        conc_errs: list = []

        def _conc_worker(i):
            try:
                cl = conc_cluster.client()
                cl.execute("USE conc")
                mine = []
                for j in range(creps):
                    t0 = time.perf_counter()
                    r = cl.execute(_conc_stmt(i, j))
                    dt = time.perf_counter() - t0
                    if r.error is not None:
                        conc_errs.append(r.error)
                        return
                    mine.append(dt)
                with lat_lock:
                    conc_lats.extend(mine)
            except Exception as ex:  # noqa: BLE001
                conc_errs.append(repr(ex))

        t0 = time.perf_counter()
        ths = [_threading.Thread(target=_conc_worker, args=(i,))
               for i in range(cthreads)]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        conc_wall = time.perf_counter() - t0
        assert not conc_errs, conc_errs[:3]
        snap1 = _cstats().snapshot()
        conc_lats.sort()
        ncl = len(conc_lats)

        def _pq(p):
            return conc_lats[min(ncl - 1, int(ncl * p / 100))]

        conc_queue_us = _qwait_us(snap1) - _qwait_us(snap0)
        conc_total_us = sum(conc_lats) * 1e6
        concurrency = {
            "threads": cthreads,
            "stmts": ncl,
            "statement_mix": "alternating 1-hop GO / 1-hop MATCH",
            "persons": cn,
            "replica_factor": 3,
            "p50_ms": round(_pq(50) * 1e3, 2),
            "p95_ms": round(_pq(95) * 1e3, 2),
            "p99_ms": round(_pq(99) * 1e3, 2),
            "qps": round(ncl / conc_wall, 1),
            "wall_s": round(conc_wall, 2),
            # the wait-vs-run decomposition item 2 is judged by: how
            # much of the summed statement latency was spent QUEUED on
            # the device dispatch gate
            "queue_wait_us_total": int(conc_queue_us),
            "queue_wait_share": round(conc_queue_us / conc_total_us, 4)
            if conc_total_us else 0.0,
        }
    finally:
        conc_cluster.stop()
    # watchdog + live-registry overhead A/B on the north-star
    # single-query config (workload_plane_enabled off = register
    # nothing; the watchdog thread keeps scanning either way) —
    # acceptance bar: <= 2%
    from nebula_tpu.exec.engine import QueryEngine as _WlQE
    from nebula_tpu.utils.config import get_config as _wl_cfg
    wl_eng = _WlQE(store)
    wl_sess = wl_eng.new_session()
    wl_eng.execute(wl_sess, "USE snb")
    wl_q = f"GO FROM {seed_list} OVER KNOWS YIELD dst(edge) AS d"

    def _wl_p50(enabled: bool) -> float:
        _wl_cfg().set_dynamic("workload_plane_enabled", enabled)
        wl_eng.execute(wl_sess, wl_q)             # warm
        ol = []
        for _ in range(40):
            t0 = time.perf_counter()
            r = wl_eng.execute(wl_sess, wl_q)
            ol.append(time.perf_counter() - t0)
            assert r.error is None, r.error
        return _median(ol)

    try:
        wl_off = _wl_p50(False)
        wl_on = _wl_p50(True)
    finally:
        _wl_cfg().dynamic_layer.pop("workload_plane_enabled", None)
    concurrency["workload_off_p50_ms"] = round(wl_off * 1e3, 3)
    concurrency["workload_on_p50_ms"] = round(wl_on * 1e3, 3)
    concurrency["workload_overhead_pct"] = round(
        max((wl_on - wl_off) / wl_off, 0.0) * 100.0, 2) \
        if wl_off > 0 else 0.0

    # insights-plane overhead A/B on the same query (ISSUE 16):
    # insights_enabled off = no fingerprint / no registry update; the
    # acceptance bar is the same <= 2%
    def _ins_p50(enabled: bool) -> float:
        _wl_cfg().set_dynamic("insights_enabled", enabled)
        wl_eng.execute(wl_sess, wl_q)             # warm
        ol = []
        for _ in range(40):
            t0 = time.perf_counter()
            r = wl_eng.execute(wl_sess, wl_q)
            ol.append(time.perf_counter() - t0)
            assert r.error is None, r.error
        return _median(ol)

    try:
        ins_off = _ins_p50(False)
        ins_on = _ins_p50(True)
    finally:
        _wl_cfg().dynamic_layer.pop("insights_enabled", None)
    concurrency["insights_off_p50_ms"] = round(ins_off * 1e3, 3)
    concurrency["insights_on_p50_ms"] = round(ins_on * 1e3, 3)
    concurrency["insights_overhead_pct"] = round(
        max((ins_on - ins_off) / ins_off, 0.0) * 100.0, 2) \
        if ins_off > 0 else 0.0

    # ---- overload block (ISSUE 10): goodput-vs-offered-load curve at
    # 1×/2×/4× estimated capacity against a live 3-replica cluster
    # with the admission plane armed.  The headline: at 4× offered
    # load goodput stays ≥ 70% of the 1× level, every surfaced shed is
    # a structured E_OVERLOAD with a retry-after hint, and the control
    # lane (SHOW QUERIES) keeps answering (its p99 reported per level).
    # The overload CHAOS schedules stay behind the `chaos` marker
    # (tests/chaos/test_overload.py) — this block is fault-free load.
    _mark("config overload: admission goodput sweep 1x/2x/4x")
    from nebula_tpu.tools.overload_bench import run_sweep as _ovl_sweep
    overload = _ovl_sweep(
        persons=int(os.environ.get("NEBULA_BENCH_OVL_PERSONS", 1200)),
        cal_threads=int(os.environ.get("NEBULA_BENCH_OVL_THREADS", 6)),
        duration_s=float(os.environ.get("NEBULA_BENCH_OVL_SECS", 3.0)),
        tpu_runtime=rt)

    # ---- batching block (ISSUE 15): multi-lane batched dispatch A/B —
    # the same small-GO offered-load sweep with batch_max_lanes off vs
    # on.  Headlines: dispatches_per_stmt_on (< 0.5 = statements share
    # launches), queue_wait_share_off_over_on (≥ 2 = the dispatch gate
    # stops being the bottleneck), goodput rising (not falling) with
    # offered load, rows byte-identical on vs off.
    _mark("config batching: multi-lane batched dispatch A/B sweep")
    from nebula_tpu.tools.overload_bench import (
        batch_sweep as _batch_sweep)
    batching = _batch_sweep(
        persons=int(os.environ.get("NEBULA_BENCH_BATCH_PERSONS",
                                   1200)),
        threads=int(os.environ.get("NEBULA_BENCH_BATCH_THREADS", 8)),
        duration_s=float(os.environ.get("NEBULA_BENCH_BATCH_SECS",
                                        3.0)),
        lanes=int(os.environ.get("NEBULA_BENCH_BATCH_LANES", 16)),
        tpu_runtime=rt)

    # ---- read_scaleout block (ISSUE 11): goodput-vs-replica-count on
    # a read-heavy mix.  1 storaged / rf=1 leader-only vs 3 storaged /
    # rf=3 at follower consistency with the bounded storaged inbox
    # armed — the acceptance number is qps_3r_vs_1r (bar: >= 2.0).
    # Also: read QPS per consistency level, follower_read share,
    # time-to-first-successful-read after a hard leader kill, and the
    # result cache serving a hot repeated read with identical rows.
    _mark("config read_scaleout: replica-count read sweep 1r vs 3r")
    from nebula_tpu.tools.overload_bench import (
        read_scaleout_sweep as _read_sweep)
    read_scaleout = _read_sweep(
        persons=int(os.environ.get("NEBULA_BENCH_READS_PERSONS",
                                   1000)),
        threads=int(os.environ.get("NEBULA_BENCH_READS_THREADS", 12)),
        duration_s=float(os.environ.get("NEBULA_BENCH_READS_SECS",
                                        3.0)),
        tpu_runtime=rt)

    # ---- htap block (ISSUE 19): write storm + read storm A/B — the
    # same sustained-write workload with the device-resident delta-CSR
    # off (every fresh read pays a graph-sized re-export + re-pin) and
    # on (commit groups append into the padded delta; reads merge
    # base + delta each hop).  Headlines: read_goodput_on_over_off
    # (>= 2.0 at comparable staleness — or comparable goodput at
    # >= 5x lower fresh_read_lag_ms) and repin_avoided_share (> 0:
    # the storm rode the delta, not the re-pin path).
    _mark("config htap: write-storm + read-storm delta-CSR A/B")
    from nebula_tpu.tools.overload_bench import (
        htap_sweep as _htap_sweep)
    htap = _htap_sweep(
        persons=int(os.environ.get("NEBULA_BENCH_HTAP_PERSONS",
                                   900)),
        writers=int(os.environ.get("NEBULA_BENCH_HTAP_WRITERS", 2)),
        readers=int(os.environ.get("NEBULA_BENCH_HTAP_READERS", 6)),
        duration_s=float(os.environ.get("NEBULA_BENCH_HTAP_SECS",
                                        3.0)),
        tpu_runtime=rt)

    # ---- fleet block (ISSUE 20): coordinator scale-out + fleet QoS —
    # a 10k-session storm over 3 graphds, then the same mixed GO/MATCH
    # offered load against 1 coordinator vs the fleet of 3 under the
    # same per-coordinator statement capacity
    # (graph_statement_capacity_qps, calibrated below the host's raw
    # throughput), then a scarce-slot DWRR phase with an aggressor
    # tenant.  Headlines: fleet_goodput_x (>= 2.5) and dwrr_share_held
    # (vip admitted share within 0.15 of its 3:1 weight under a 2x
    # aggressor).
    _mark("config fleet: 3-graphd scale-out + session storm + DWRR")
    from nebula_tpu.tools.overload_bench import (
        fleet_sweep as _fleet_sweep)
    fleet = _fleet_sweep(
        persons=int(os.environ.get("NEBULA_BENCH_FLEET_PERSONS",
                                   1200)),
        workers=int(os.environ.get("NEBULA_BENCH_FLEET_THREADS", 18)),
        duration_s=float(os.environ.get("NEBULA_BENCH_FLEET_SECS",
                                        3.0)),
        n_sessions=int(os.environ.get("NEBULA_BENCH_FLEET_SESSIONS",
                                      10_000)),
        tpu_runtime=rt)

    # ---- self_heal block (ISSUE 14): kill one of a part's three
    # replicas under live mixed load and measure the repair plane —
    # time_to_full_redundancy (kill → part map fully rf=3 on live
    # hosts, no operator action) and the goodput dip while the
    # replacement replicas snapshot-install.  Acceptance: healed with
    # acked_lost == wrong_rows == 0.
    _mark("config self_heal: kill-one-of-three auto-repair under load")
    from nebula_tpu.tools.repair_bench import run_self_heal as _heal
    self_heal = _heal(
        rows=int(os.environ.get("NEBULA_BENCH_HEAL_ROWS", 300)),
        duration_s=float(os.environ.get("NEBULA_BENCH_HEAL_SECS",
                                        8.0)),
        workers=int(os.environ.get("NEBULA_BENCH_HEAL_THREADS", 4)))

    # ---- multichip block (ISSUE 17): mesh-native sharded execution
    # A/B — HBM scale-out proof (graph 4x the per-device budget:
    # single-chip pin refuses, N-shard pin accepts, per-shard gauges
    # sum to the pinned total), GO-3-step rows byte-identical 1-shard
    # vs N-shard vs numpy oracle, goodput + all_to_all bytes/hop.
    # Runs IN THIS PROCESS on the devices it already holds; a one-chip
    # host has no sharded arm to run and says so.
    if device["count"] >= 2:
        _mark(f"config multichip: 1-vs-{min(8, device['count'])}-shard "
              f"mesh execution A/B")
        from nebula_tpu.tools.multichip_bench import (
            multichip_sweep as _mc_sweep)
        multichip = _mc_sweep(
            persons=int(os.environ.get("NEBULA_BENCH_MULTICHIP_PERSONS",
                                       120_000)),
            repeats=int(os.environ.get("NEBULA_BENCH_MULTICHIP_REPEATS",
                                       5)))
    else:
        multichip = {"skipped": "one device: no sharded arm"}

    # ---- algo block (ISSUE 13): device vs numpy-host oracle A/B per
    # CALL algo.* algorithm (pagerank / wcc / sssp) on a north-star-
    # shaped social array graph, with per-iteration device timing.
    # Rows are asserted against the oracles (exact for wcc/sssp,
    # max |Δrank| ≤ 1e-8 for pagerank); overall_speedup = summed host
    # time / summed device time is the acceptance number.
    _mark("config algo: CALL algo.* device vs host oracle A/B")
    from nebula_tpu.tools.algo_bench import run_suite as _algo_suite
    algo_block = _algo_suite(
        persons=int(os.environ.get("NEBULA_BENCH_ALGO_PERSONS",
                                   min(n_persons, 300_000))),
        degree=int(os.environ.get("NEBULA_BENCH_ALGO_DEGREE",
                                  degree)),
        parts=parts, tpu_runtime=rt,
        repeats=int(os.environ.get("NEBULA_BENCH_ALGO_REPEATS", 3)))
    _algs = [v for k, v in algo_block.items() if k != "graph"]
    algo_block["overall_speedup"] = round(
        sum(a["host_s"] for a in _algs)
        / max(sum(a["device_s"] for a in _algs), 1e-9), 3)
    algo_block["rows_match_all"] = all(a["rows_match"]
                                      for a in _algs)

    # VERDICT r3 item 2: the driver tails stdout into a small buffer, so
    # the headline must be COMPACT and LAST.  Full detail goes to
    # BENCH_DETAIL.json next to this script.
    # ISSUE 2 control-plane evidence: the engine configs above ran
    # their repeats through the plan cache (parse/plan skipped on every
    # repeat) and every RPC rode the pipelined pool — surface the
    # counters next to the timings they explain
    from nebula_tpu.utils.stats import stats as _stats
    _snap = _stats().snapshot()
    hot_path = {
        "plan_cache_hits": _snap.get("plan_cache_hits", 0),
        "plan_cache_misses": _snap.get("plan_cache_misses", 0),
        "rpc_pool_size": _snap.get("rpc_pool_size", 0),
        # ISSUE 4 observability: how often the columnar MATCH pipeline
        # fused vs bailed (labeled reasons live in /metrics)
        "match_pipeline_fused": _snap.get("match_pipeline_fused", 0),
        "match_pipeline_fused_plans":
            _snap.get("match_pipeline_fused_plans", 0),
        "match_pipeline_fallback": sum(
            v for k, v in _snap.items()
            if k.startswith("match_pipeline_fallback")),
    }
    # ---- observability block (ISSUE 8): flight-recorder overhead A/B
    # (sampling ON at rate 1.0 — every statement retained — vs OFF) on
    # a small host-path statement where fixed per-statement cost is
    # most visible.  Medians over enough repeats to beat VM noise; the
    # acceptance bar is ≤ 2% on the north-star config, where the
    # per-statement work dwarfs the recorder's dict inserts.
    _mark("config obs: flight recorder overhead A/B")
    from nebula_tpu.exec.engine import QueryEngine as _ObsQE
    from nebula_tpu.utils.config import get_config as _obs_cfg
    from nebula_tpu.utils.flight import flight_recorder as _obs_fr
    from nebula_tpu.utils.slo import slo_engine as _obs_slo
    obs_eng = _ObsQE(store)
    obs_sess = obs_eng.new_session()
    obs_eng.execute(obs_sess, "USE snb")
    obs_q = (f"GO FROM {seed_list} OVER KNOWS YIELD dst(edge) AS d")
    obs_rep = 40

    def _obs_p50(rate: float) -> float:
        _obs_cfg().set_dynamic("flight_sample_rate", rate)
        obs_eng.execute(obs_sess, obs_q)          # warm
        ol = []
        for _ in range(obs_rep):
            t0 = time.perf_counter()
            rs = obs_eng.execute(obs_sess, obs_q)
            ol.append(time.perf_counter() - t0)
            assert rs.error is None, rs.error
        return _median(ol)

    try:
        off_p50 = _obs_p50(0.0)
        on_p50 = _obs_p50(1.0)
    finally:
        _obs_cfg().dynamic_layer.pop("flight_sample_rate", None)
    obs_overhead = max((on_p50 - off_p50) / off_p50, 0.0) \
        if off_p50 > 0 else 0.0
    slo_rows = _obs_slo().burn_rates()
    observability = {
        "flight_off_p50_ms": round(off_p50 * 1e3, 3),
        "flight_on_p50_ms": round(on_p50 * 1e3, 3),
        "flight_overhead_pct": round(obs_overhead * 100.0, 2),
        "flight_entries": len(_obs_fr().list(limit=10_000)),
        "slo_burn_1h": {
            f"{r['objective']}": r["burn"] for r in slo_rows
            if r["window"] == "1h"},
        "scheduler_parallel_plans":
            _stats().snapshot().get("scheduler_parallel_plans", 0),
        "flight_records": sum(
            v for k, v in _stats().snapshot().items()
            if k.startswith("flight_records")),
    }
    # ---- fault_recovery block (ISSUE 5 satellite): two seeded chaos
    # schedules over a live 3-replica cluster — the highest-impact crash
    # (leader kill mid-workload) and the dedup window's home turf (acked
    # replies killed).  Reported: recovery time (faults stop → replicas
    # byte-identical + TOSS journals drained) and retry amplification
    # (internal re-sends per acked statement, from the deterministic
    # counters — noise-immune).  Runs AFTER the hot-path snapshot above:
    # the chaos harness resets process-wide stats per cluster.
    _mark("config fault: seeded chaos schedules (chaos_bench)")
    from nebula_tpu.tools.chaos_bench import run as _chaos_bench
    cb = _chaos_bench(schedules=["leader_kill", "reply_loss"], writes=30)
    fault_recovery = {
        "schedules": sorted(cb["schedules"]),
        "invariants_ok": cb["invariants_ok"],
        "worst_recovery_s": cb["worst_recovery_s"],
        "retry_amplification": cb["retry_amplification"],
        "leader_kill_to_drained_s":
            cb["schedules"]["leader_kill"]["kill_to_drained_s"],
        "acked_writes": sum(s["acked"] for s in cb["schedules"].values()),
        "failed_writes": sum(s["failed"] for s in cb["schedules"].values()),
        "dedup_hits":
            sum(s["counters"]["dedup_hits"]
                for s in cb["schedules"].values()),
        "faults_fired": sum(s["faults_fired"]
                            for s in cb["schedules"].values()),
    }
    detail_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_DETAIL.json")
    # ---- pinned, noise-immune regression block (VERDICT r5 weak #8 /
    # ISSUE 4 satellite): fixed-seed graph, converged (pinned) padded
    # buckets, kernel-only per-hop op counts from the DETERMINISTIC work
    # counters (byte-identical across runs, asserted above) alongside
    # the noisy edges/s — r6-vs-r5 diffs these counts to tell a real
    # regression from VM weather.  The previous round's block is carried
    # one deep so the comparison ships in-band.
    prev_reg = None
    try:
        with open(detail_path) as f:
            prev_reg = json.load(f).get("regression")
            if prev_reg is not None:
                prev_reg.pop("previous", None)
    except (OSError, ValueError):
        pass
    regression = {
        "schema": 1,
        "inputs": {"persons": n_persons, "avg_degree": degree,
                   "parts": parts, "datagen_seed": 7, "hops": 3,
                   "seeds": n_seeds, "platform": platform},
        "buckets": {"EB": cfg6_st.e_cap},
        "per_hop_edges": [int(x) for x in cfg6_st.hop_edges],
        "per_hop_frontier": [int(x) for x in cfg6_st.frontier_sizes],
        "work_counters": work1,
        "work_counters_identical": True,
        "edges_per_run": edges,
        "kernel_p50_ms": round(_median(klat) * 1e3, 2),
        "kernel_eps": round(tpu_kernel_eps, 1),
    }
    if prev_reg is not None:
        regression["previous"] = prev_reg
        same = (prev_reg.get("inputs") == regression["inputs"]
                and prev_reg.get("per_hop_edges")
                == regression["per_hop_edges"]
                and prev_reg.get("work_counters")
                == regression["work_counters"])
        regression["work_identical_to_previous"] = bool(same)
    detail = {
        "platform": platform,
        "hot_path": hot_path,
        "device": device,
        "north_star_graph": {"persons": n_persons, "avg_degree": degree,
                             "parts": parts,
                             "edges": int(arrs["src"].size),
                             "build_s": round(big_build_s, 2)},
        "small_graph": {"persons": small_n,
                        "build_s": round(small_build_s, 2),
                        "ldbc_import": import_info},
        "baseline": "numpy_csr_1core_interleaved_median",
        "kernel_eps": round(tpu_kernel_eps, 1),
        "kernel_vs_cpu": round(tpu_kernel_eps / cpu_eps, 3),
        "device_hbm_bytes": ns_hbm_bytes,
        "supernode_skew": skew,
        "regression": regression,
        "fault_recovery": fault_recovery,
        "observability": observability,
        "concurrency": concurrency,
        "overload": overload,
        "batching": batching,
        "read_scaleout": read_scaleout,
        "htap": htap,
        "fleet": fleet,
        "self_heal": self_heal,
        "algo": algo_block,
        "multichip": multichip,
        "configs": configs,
    }
    with open(detail_path, "w") as f:
        json.dump(detail, f, indent=1)
    _mark(f"detail written to {detail_path}")
    hl = {
        "metric": "traversed_edges_per_sec_go3step_e2e",
        "value": round(tpu_e2e_eps, 1),
        "unit": "edges/s",
        "vs_baseline": round(tpu_e2e_eps / cpu_eps, 3),
        # comparator provenance (VERDICT r4 weak #5): vs_baseline has
        # meant different things across rounds; name it in-band
        "baseline": "numpy_csr_1core_interleaved_median",
        "client_vs_baseline": round(tpu_client_eps / cpu_eps, 3),
        "platform": platform,
        "kernel_vs_cpu": round(tpu_kernel_eps / cpu_eps, 3),
        "identical_rows": True,
        # noise-immune regression signal (full schema in detail JSON)
        "work_edges": work1["edges_traversed"],
        # fused-pipeline IC A/B (ISSUE 4): host_p50/device_p50 per config
        "ic_dev_x": [configs["ic5"]["device_vs_host"],
                     configs["ic9"]["device_vs_host"]],
    }
    # ISSUE 13: CALL algo.* device-vs-oracle aggregate (detail has
    # the per-algorithm split + per-iteration timings)
    hl["algo_x"] = algo_block["overall_speedup"]
    if batching.get("dispatches_per_stmt_on") is not None:
        # ISSUE 15: shared multi-lane launches — mean device launches
        # per statement with batching on (detail has the full A/B:
        # queue_wait_share off/on, goodput curve, lanes per batch)
        hl["batch_disp_per_stmt"] = batching["dispatches_per_stmt_on"]
    if htap.get("read_goodput_on_over_off") is not None:
        # ISSUE 19: device-resident delta-CSR — fresh-read goodput
        # under a sustained write storm, delta on vs off (detail has
        # the full A/B: staleness p50/p99, repin_avoided_share,
        # compactions)
        hl["htap_goodput_x"] = htap["read_goodput_on_over_off"]
        hl["fresh_read_lag_ms"] = htap["fresh_read_lag_ms"]
        hl["repin_avoided_share"] = htap["repin_avoided_share"]
    if multichip.get("speedup_Nshard_vs_1") is not None:
        # ISSUE 17: mesh-native sharded execution — N-shard vs 1-shard
        # goodput (detail has the HBM scale-out proof, parity verdicts
        # and exchange bytes/hop)
        hl["multichip_x"] = multichip["speedup_Nshard_vs_1"]
    if fleet.get("fleet_goodput_x") is not None:
        # ISSUE 20: 3-coordinator goodput vs one under the same
        # per-coordinator capacity, plus the DWRR share-hold verdict
        # (detail has the session storm, both arms, the tenant split)
        hl["fleet_goodput_x"] = fleet["fleet_goodput_x"]
        hl["dwrr_held"] = bool(fleet.get("dwrr_share_held"))
    if self_heal.get("healed"):
        # ISSUE 14: kill-one-of-three auto-repair — seconds from the
        # kill to full redundancy with zero acked-write loss (detail
        # has the goodput phases + plan outcomes)
        hl["heal_s"] = self_heal["time_to_full_redundancy_s"]
    headline = json.dumps(hl)
    assert len(headline) <= 500, len(headline)
    print(headline, flush=True)


if __name__ == "__main__":
    main()
