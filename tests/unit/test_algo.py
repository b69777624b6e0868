"""Graph-analytics plane (ISSUE 13): `CALL algo.*` on the shared
vertex-program engine.

Covers: statement surface (parse/validate/plan), seeded oracle parity
(device PageRank/WCC/SSSP vs the independent numpy oracles — exact for
WCC/SSSP, documented tolerance + deterministic order for PageRank),
kill/deadline landing BETWEEN iterations, admission behavior (below-
interactive band, queued-statement deadline eviction), flight-recorder
forced capture for killed/shed algo statements, live SHOW QUERIES
per-iteration progress, the BFS refactor regression (device FIND
SHORTEST PATH rows still byte-identical to the host oracle through the
shared frontier steps).
"""
import random
import threading
import time

import numpy as np
import pytest

from nebula_tpu.core.value import NULL
from nebula_tpu.exec.engine import QueryEngine
from nebula_tpu.graphstore.schema import PropDef, PropType
from nebula_tpu.graphstore.store import GraphStore
from nebula_tpu.utils.admission import (admission, is_analytic_stmt,
                                        is_control_stmt)
from nebula_tpu.utils.config import get_config
from nebula_tpu.utils.failpoints import fail
from nebula_tpu.utils.flight import flight_recorder
from nebula_tpu.utils.stats import stats

tpu = pytest.importorskip("nebula_tpu.tpu")
from nebula_tpu.tpu import TpuRuntime, make_mesh  # noqa: E402

P = 4

PAGERANK_TOL = 1e-8     # documented |Δrank| bar vs the oracle


def algo_store(seed=0, n=80, avg_deg=4, spacename="ag",
               neg_weight=False):
    """Seeded random graph with a non-negative int weight prop (w),
    occasionally-NULL weights, a second edge type, and an isolated
    + dangling vertex so the corner paths (no out-edges, no edges at
    all) are always exercised."""
    rng = random.Random(seed)
    st = GraphStore()
    st.create_space(spacename, partition_num=P, vid_type="INT64")
    st.catalog.create_tag(spacename, "person", [
        PropDef("age", PropType.INT64)])
    st.catalog.create_edge(spacename, "knows", [
        PropDef("w", PropType.INT64)])
    st.catalog.create_edge(spacename, "likes", [
        PropDef("w", PropType.INT64)])
    for v in range(n):
        st.insert_vertex(spacename, v, "person", {"age": v})
    lo = -5 if neg_weight else 0
    for v in range(n - 2):          # n-2: dangling, n-1: isolated
        for _ in range(rng.randint(0, avg_deg * 2)):
            d = rng.randrange(n - 1)
            w = rng.randint(lo, 9) if rng.random() > 0.1 else NULL
            st.insert_edge(spacename, v, "knows", d, rng.randint(0, 1),
                           {"w": w})
        if rng.random() > 0.6:
            st.insert_edge(spacename, v, "likes", rng.randrange(n - 1),
                           0, {"w": rng.randint(0, 9)})
    return st


@pytest.fixture(scope="module")
def rt():
    return TpuRuntime(make_mesh(P))


@pytest.fixture(scope="module")
def eng(rt):
    st = algo_store(1)
    e = QueryEngine(st, tpu_runtime=rt)
    s = e.new_session()
    assert e.execute(s, "USE ag").ok
    return e


@pytest.fixture()
def sess(eng):
    s = eng.new_session()
    eng.execute(s, "USE ag")
    return s


@pytest.fixture()
def clean():
    fail.reset()
    admission().reset()
    yield
    fail.reset()
    admission().reset()
    for k in ("max_running_queries", "admission_queue_capacity",
              "query_timeout_secs"):
        get_config().dynamic_layer.pop(k, None)


def q(eng, sess, text):
    rs = eng.execute(sess, text)
    assert rs.error is None, f"{text} -> {rs.error}"
    return rs


# -- statement surface ------------------------------------------------------


def test_parse_plan_explain(eng, sess):
    rs = q(eng, sess, "EXPLAIN CALL algo.pagerank(max_iter=5) "
                      "YIELD vid, rank AS r")
    assert "CallAlgo" in rs.data.rows[0][0]


def test_yield_aliases_and_projection(eng, sess):
    rs = q(eng, sess, "CALL algo.pagerank(max_iter=2) "
                      "YIELD rank AS r")
    assert rs.data.column_names == ["r"]
    assert all(isinstance(v[0], float) for v in rs.data.rows)


def test_default_yield_is_full_width(eng, sess):
    rs = q(eng, sess, "CALL algo.wcc()")
    assert rs.data.column_names == ["vid", "component"]


@pytest.mark.parametrize("text,frag", [
    ("CALL algo.nope()", "unknown algorithm"),
    ("CALL algo.pagerank(bogus=1)", "unknown parameter"),
    ("CALL algo.sssp()", "requires parameter `src'"),
    ("CALL algo.pagerank() YIELD nope", "cannot YIELD"),
    ("CALL notalgo.pagerank()", "unknown procedure module"),
    ('CALL algo.pagerank(edge_types="nosuch")', "not found"),
    ("CALL algo.pagerank() YIELD rank + 1", "bare output column"),
])
def test_validation_errors(eng, sess, text, frag):
    rs = eng.execute(sess, text)
    assert rs.error is not None and frag in rs.error, (text, rs.error)


def test_duplicate_param_is_syntax_error(eng, sess):
    rs = eng.execute(sess, "CALL algo.pagerank(max_iter=1, max_iter=2)")
    assert rs.error is not None and "duplicate parameter" in rs.error


def test_bad_param_values(eng, sess):
    for text, frag in [
        ("CALL algo.pagerank(damping=2.0)", "damping"),
        ("CALL algo.pagerank(max_iter=-1)", "max_iter"),
        ('CALL algo.pagerank(mode="wat")', "mode"),
        ('CALL algo.sssp(src=0, direction="up")', "direction"),
    ]:
        rs = eng.execute(sess, text)
        assert rs.error is not None and frag in rs.error, (text,
                                                          rs.error)


def test_negative_weights_refused(rt):
    st = algo_store(9, neg_weight=True)
    e = QueryEngine(st, tpu_runtime=rt)
    s = e.new_session()
    e.execute(s, "USE ag")
    rs = e.execute(s, 'CALL algo.sssp(src=0, weight="w")')
    assert rs.error is not None and "non-negative" in rs.error


def test_sssp_unknown_source_is_empty(eng, sess):
    rs = q(eng, sess, "CALL algo.sssp(src=987654)")
    assert rs.data.rows == []


# -- oracle parity (the tentpole contract) ----------------------------------


def _rows(eng, sess, text):
    return q(eng, sess, text).data.rows


@pytest.mark.parametrize("seed", [2, 3, 4])
def test_wcc_device_matches_oracle(rt, seed):
    st = algo_store(seed)
    e = QueryEngine(st, tpu_runtime=rt)
    s = e.new_session()
    e.execute(s, "USE ag")
    dev = _rows(e, s, 'CALL algo.wcc(mode="device")')
    host = _rows(e, s, 'CALL algo.wcc(mode="host")')
    assert dev == host                      # union-find vs label prop
    assert len(dev) == 80                   # every vertex reported
    # the isolated vertex is its own component
    comp = dict(dev)
    assert comp[79] == 79


@pytest.mark.parametrize("seed", [2, 3, 4])
@pytest.mark.parametrize("weight", [None, "w"])
def test_sssp_device_matches_oracle(rt, seed, weight):
    st = algo_store(seed)
    e = QueryEngine(st, tpu_runtime=rt)
    s = e.new_session()
    e.execute(s, "USE ag")
    warg = f', weight="{weight}"' if weight else ""
    dev = _rows(e, s, f'CALL algo.sssp(src=0{warg}, mode="device")')
    host = _rows(e, s, f'CALL algo.sssp(src=0{warg}, mode="host")')
    assert dev == host                      # Bellman frontier vs Dijkstra
    d = dict(dev)
    assert d[0] == 0.0
    assert 79 not in d                      # isolated: unreached


@pytest.mark.parametrize("seed", [2, 3])
def test_pagerank_device_matches_oracle(rt, seed):
    st = algo_store(seed)
    e = QueryEngine(st, tpu_runtime=rt)
    s = e.new_session()
    e.execute(s, "USE ag")
    dev = _rows(e, s, 'CALL algo.pagerank(max_iter=30, tol=0.0, '
                      'mode="device")')
    host = _rows(e, s, 'CALL algo.pagerank(max_iter=30, tol=0.0, '
                       'mode="host")')
    assert [r[0] for r in dev] == [r[0] for r in host]   # same vid order
    diffs = [abs(a[1] - b[1]) for a, b in zip(dev, host)]
    assert max(diffs) <= PAGERANK_TOL
    # deterministic ranking order: rounding inside the tolerance, the
    # two sides rank vertices identically (ties broken by vid)
    def ranking(rows):
        return [v for v, _ in sorted(rows,
                                     key=lambda r: (-round(r[1], 6),
                                                    r[0]))]
    assert ranking(dev) == ranking(host)
    # ranks form a probability distribution over the real vertices
    assert abs(sum(r[1] for r in dev) - 1.0) < 1e-6


def test_pagerank_deterministic_across_runs(eng, sess):
    a = _rows(eng, sess, "CALL algo.pagerank(max_iter=10, tol=0.0)")
    b = _rows(eng, sess, "CALL algo.pagerank(max_iter=10, tol=0.0)")
    assert a == b                           # bit-identical run-to-run


def test_edge_types_restriction(rt):
    st = algo_store(5)
    e = QueryEngine(st, tpu_runtime=rt)
    s = e.new_session()
    e.execute(s, "USE ag")
    both = _rows(e, s, 'CALL algo.wcc(mode="device")')
    only = _rows(e, s, 'CALL algo.wcc(edge_types="knows", '
                       'mode="device")')
    host = _rows(e, s, 'CALL algo.wcc(edge_types="knows", '
                       'mode="host")')
    assert only == host
    # dropping `likes` can only split components, never merge them
    nc = lambda rows: len({c for _, c in rows})
    assert nc(only) >= nc(both)


def test_deleted_vertex_excluded(rt):
    st = algo_store(6)
    e = QueryEngine(st, tpu_runtime=rt)
    s = e.new_session()
    e.execute(s, "USE ag")
    q(e, s, "DELETE VERTEX 5")
    rows = _rows(e, s, "CALL algo.wcc()")
    assert 5 not in {r[0] for r in rows}
    assert 5 not in {r[1] for r in rows}    # nor as a component id


def test_host_mode_without_runtime():
    """No device runtime at all: auto mode runs the oracles."""
    st = algo_store(7)
    e = QueryEngine(st)                      # no tpu_runtime
    s = e.new_session()
    e.execute(s, "USE ag")
    rows = _rows(e, s, "CALL algo.wcc()")
    assert len(rows) == 80
    rs = e.execute(s, 'CALL algo.wcc(mode="device")')
    assert rs.error is not None and "no device runtime" in rs.error


# -- long-running statement contract (kill / deadline / progress) ----------


def _run_async(eng, sess, text):
    box = {}

    def run():
        box["rs"] = eng.execute(sess, text)

    t = threading.Thread(target=run, daemon=True)
    t.start()
    return t, box


def _wait_for(pred, timeout=10.0, msg="condition"):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        got = pred()
        if got:
            return got
        time.sleep(0.01)
    pytest.fail(f"timed out waiting for {msg}")


def test_kill_lands_between_iterations(eng, clean):
    s = eng.new_session()
    eng.execute(s, "USE ag")
    fail.arm("algo:iter", "1000000*delay(0.05)")
    flight_recorder().clear()
    t, box = _run_async(
        eng, s, "CALL algo.pagerank(max_iter=10000, tol=0.0)")
    from nebula_tpu.utils.workload import live_registry
    lq = _wait_for(
        lambda: next((x for x in live_registry().snapshot()
                      if "algo.pagerank[iter" in x["operator"]), None),
        msg="live iteration progress")
    assert "active_frontier=" in lq["operator"]
    assert eng.kill_running(qid=lq["qid"])
    t.join(timeout=10)
    assert not t.is_alive()
    assert box["rs"].error == "ExecutionError: query was killed"
    # forced flight capture, classified `killed`, kind CallAlgo
    ent = next(e for e in flight_recorder().list(limit=10)
               if e["kind"] == "CallAlgo")
    assert ent["status"] == "killed"


def test_deadline_lands_between_iterations(eng, clean):
    get_config().set_dynamic("query_timeout_secs", 0.3)
    s = eng.new_session()
    eng.execute(s, "USE ag")
    fail.arm("algo:iter", "1000000*delay(0.05)")
    before = stats().snapshot().get("query_deadline_exceeded", 0)
    rs = eng.execute(s, "CALL algo.pagerank(max_iter=10000, tol=0.0)")
    assert rs.error is not None and rs.error.startswith(
        "E_QUERY_TIMEOUT")
    assert stats().snapshot()["query_deadline_exceeded"] == before + 1


def test_deadline_lands_in_host_oracle_pagerank(eng, clean):
    """The iterative HOST oracle honors the cancel contract too: the
    console path (no device runtime) must not hang a KILL/timeout
    until 10M power iterations finish."""
    get_config().set_dynamic("query_timeout_secs", 0.3)
    s = eng.new_session()
    eng.execute(s, "USE ag")
    t0 = time.monotonic()
    rs = eng.execute(s, 'CALL algo.pagerank(max_iter=10000000, '
                        'tol=0.0, mode="host")')
    assert rs.error is not None and rs.error.startswith(
        "E_QUERY_TIMEOUT")
    assert time.monotonic() - t0 < 5.0


def test_show_queries_displays_iteration_progress(eng, clean):
    s = eng.new_session()
    eng.execute(s, "USE ag")
    s2 = eng.new_session()
    fail.arm("algo:iter", "1000000*delay(0.05)")
    t, box = _run_async(
        eng, s, "CALL algo.wcc(max_iter=10000)")

    def probe():
        rs = eng.execute(s2, "SHOW QUERIES")
        for r in rs.data.rows:
            if "algo.wcc[iter" in r[5]:
                return r
        return None
    row = _wait_for(probe, msg="SHOW QUERIES algo progress")
    assert "active_frontier=" in row[5]
    assert row[4] == "RUNNING"
    fail.reset()                 # let it finish quickly
    t.join(timeout=20)
    assert box["rs"].error is None


# -- admission: below-interactive band --------------------------------------


def test_callalgo_is_analytic_not_control():
    assert is_analytic_stmt("CallAlgo")
    assert not is_control_stmt("CallAlgo")
    assert not is_analytic_stmt("Go")


def test_analytic_queues_below_interactive(clean):
    """slots=1 busy; a queued CALL algo.* must NOT be admitted while
    an interactive statement waits, even though it enqueued first."""
    from nebula_tpu.utils import cancel as _cancel
    cfg = get_config()
    cfg.set_dynamic("max_running_queries", 1)
    cfg.set_dynamic("admission_queue_capacity", 10)
    ctl = admission()
    blocker = ctl.acquire(qid=1, session=1, kind="Go")
    assert blocker is not None and blocker.mode == "admitted"
    order = []

    def waiter(qid, sid, kind):
        with _cancel.use_cancel(kill=threading.Event()):
            tk = ctl.acquire(qid=qid, session=sid, kind=kind)
        order.append(kind)
        tk.release()

    ta = threading.Thread(target=waiter, args=(2, 2, "CallAlgo"),
                          daemon=True)
    ta.start()
    _wait_for(lambda: ctl.snapshot()["analytic_queued"] == 1,
              msg="analytic queued")
    tb = threading.Thread(target=waiter, args=(3, 3, "Go"),
                          daemon=True)
    tb.start()
    _wait_for(lambda: ctl.snapshot()["queued"] == 2,
              msg="both queued")
    blocker.release()
    ta.join(timeout=5)
    tb.join(timeout=5)
    assert order == ["Go", "CallAlgo"]


def test_queued_algo_deadline_evicted(eng, clean):
    """PR 8 deadline-aware eviction applies to the analytic band: a
    CALL algo.* whose budget expires while QUEUED fails
    E_QUERY_TIMEOUT without ever taking a slot."""
    cfg = get_config()
    cfg.set_dynamic("max_running_queries", 1)
    cfg.set_dynamic("admission_queue_capacity", 10)
    s1 = eng.new_session()
    eng.execute(s1, "USE ag")
    s2 = eng.new_session()
    eng.execute(s2, "USE ag")
    fail.arm_callable(
        "exec:node",
        lambda i, key: ("delay", 0.8) if key == "Project" else None)
    t1, b1 = _run_async(eng, s1, "YIELD 1 AS x")   # occupies the slot
    _wait_for(lambda: admission().snapshot()["running"] == 1,
              msg="slot taken")
    cfg.set_dynamic("query_timeout_secs", 0.2)
    before = stats().snapshot().get("admission_deadline_evictions", 0)
    rs = eng.execute(s2, "CALL algo.pagerank(max_iter=10000, tol=0.0)")
    cfg.dynamic_layer.pop("query_timeout_secs", None)
    assert rs.error is not None and rs.error.startswith(
        "E_QUERY_TIMEOUT")
    assert stats().snapshot()["admission_deadline_evictions"] \
        == before + 1
    fail.reset()
    t1.join(timeout=20)
    assert b1["rs"].error is None


def test_kill_evicts_queued_algo(eng, clean):
    cfg = get_config()
    cfg.set_dynamic("max_running_queries", 1)
    cfg.set_dynamic("admission_queue_capacity", 10)
    s1 = eng.new_session()
    eng.execute(s1, "USE ag")
    s2 = eng.new_session()
    eng.execute(s2, "USE ag")
    fail.arm_callable(
        "exec:node",
        lambda i, key: ("delay", 0.8) if key == "Project" else None)
    t1, b1 = _run_async(eng, s1, "YIELD 1 AS x")
    _wait_for(lambda: admission().snapshot()["running"] == 1,
              msg="slot taken")
    t2, b2 = _run_async(eng, s2,
                        "CALL algo.pagerank(max_iter=10000, tol=0.0)")
    _wait_for(lambda: admission().snapshot()["analytic_queued"] == 1,
              msg="algo queued")
    assert eng.kill_running(sid=s2.id)
    t2.join(timeout=10)
    assert b2["rs"].error == "ExecutionError: query was killed"
    fail.reset()
    t1.join(timeout=20)
    assert b1["rs"].error is None


def test_shed_algo_forces_flight_capture(eng, clean):
    """Queue full → E_OVERLOAD; the flight recorder classifies the
    shed CALL algo.* like any other statement kind (ISSUE 13
    satellite)."""
    cfg = get_config()
    cfg.set_dynamic("max_running_queries", 1)
    cfg.set_dynamic("admission_queue_capacity", 0)
    s1 = eng.new_session()
    eng.execute(s1, "USE ag")
    s2 = eng.new_session()
    eng.execute(s2, "USE ag")
    fail.arm_callable(
        "exec:node",
        lambda i, key: ("delay", 0.8) if key == "Project" else None)
    t1, b1 = _run_async(eng, s1, "YIELD 1 AS x")
    _wait_for(lambda: admission().snapshot()["running"] == 1,
              msg="slot taken")
    flight_recorder().clear()
    rs = eng.execute(s2, "CALL algo.wcc()")
    assert rs.error is not None and rs.error.startswith("E_OVERLOAD")
    assert "retry_after_ms=" in rs.error
    ent = next(e for e in flight_recorder().list(limit=10)
               if e["kind"] == "CallAlgo")
    assert ent["status"] == "shed"
    fail.reset()
    t1.join(timeout=20)
    assert b1["rs"].error is None


# -- metrics ---------------------------------------------------------------


def test_algo_metrics_emitted(eng, sess):
    snap0 = stats().snapshot()
    q(eng, sess, "CALL algo.pagerank(max_iter=3, tol=0.0)")
    snap = stats().snapshot()
    runs = {k: v for k, v in snap.items() if k.startswith("algo_runs")}
    assert any("pagerank" in k and "device" in k for k in runs)
    it_key = next(k for k in snap
                  if k.startswith("algo_iterations")
                  and "pagerank" in k)
    assert snap[it_key] - snap0.get(it_key, 0) == 3


# -- BFS refactor regression (shared frontier steps) ------------------------


def _bfs_store(seed=11, n=60):
    rng = random.Random(seed)
    st = GraphStore()
    st.create_space("bg", partition_num=P, vid_type="INT64")
    st.catalog.create_tag("bg", "t", [PropDef("x", PropType.INT64)])
    st.catalog.create_edge("bg", "e", [PropDef("w", PropType.INT64)])
    for v in range(n):
        st.insert_vertex("bg", v, "t", {"x": v})
    for v in range(n):
        for _ in range(rng.randint(3, 7)):
            st.insert_edge("bg", v, "e", rng.randrange(n),
                           rng.randint(0, 1), {"w": rng.randint(0, 9)})
    return st


@pytest.mark.parametrize("mesh_n", [P, 1])
@pytest.mark.parametrize("where", [None, "e.w > 3"])
def test_find_shortest_path_regression(mesh_n, where):
    """Byte-identical-rows regression for the BFS refactor onto the
    shared frontier steps: device FIND SHORTEST PATH rows must equal
    the host oracle's rows exactly on both kernels (sharded P-way and
    the single-chip direction-optimizing variant), filtered and
    unfiltered."""
    st = _bfs_store()
    rt = TpuRuntime(make_mesh(mesh_n))
    w = f" WHERE {where}" if where else ""
    text = (f"FIND SHORTEST PATH FROM 1, 7 TO 13, 29 OVER e{w} "
            f"UPTO 6 STEPS YIELD path AS p")
    dev_eng = QueryEngine(st, tpu_runtime=rt)
    s = dev_eng.new_session()
    dev_eng.execute(s, "USE bg")
    dev = dev_eng.execute(s, text)
    assert dev.error is None
    host_eng = QueryEngine(st)              # host oracle (no runtime)
    hs = host_eng.new_session()
    host_eng.execute(hs, "USE bg")
    host = host_eng.execute(hs, text)
    assert host.error is None
    assert list(map(repr, dev.data.rows)) == \
        list(map(repr, host.data.rows))
    if where is None:           # the filtered variant may prune to 0
        assert len(host.data.rows) > 0


@pytest.mark.slow
def test_oracle_parity_larger_sweep():
    """Slow variant: more seeds, bigger graphs, all three algorithms
    (tier-1 keeps the 3-seed small sweep above)."""
    rt = TpuRuntime(make_mesh(P))
    for seed in range(20, 24):
        st = algo_store(seed, n=400, avg_deg=6)
        e = QueryEngine(st, tpu_runtime=rt)
        s = e.new_session()
        e.execute(s, "USE ag")
        assert _rows(e, s, 'CALL algo.wcc(mode="device")') == \
            _rows(e, s, 'CALL algo.wcc(mode="host")')
        assert _rows(e, s, 'CALL algo.sssp(src=0, weight="w", '
                           'mode="device")') == \
            _rows(e, s, 'CALL algo.sssp(src=0, weight="w", '
                        'mode="host")')
        dev = _rows(e, s, 'CALL algo.pagerank(max_iter=40, tol=0.0, '
                          'mode="device")')
        host = _rows(e, s, 'CALL algo.pagerank(max_iter=40, tol=0.0, '
                           'mode="host")')
        assert max(abs(a[1] - b[1]) for a, b in zip(dev, host)) \
            <= PAGERANK_TOL
