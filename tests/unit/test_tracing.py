"""Distributed tracing + Prometheus export + work counters (ISSUE 1).

Covers the acceptance criteria:
  * a GO query through a socket-real LocalCluster produces ONE trace
    whose tree holds graphd-side executor spans, storaged-side spans
    delivered over the RPC envelope, and the device-plane
    put/dispatch/fetch phase spans;
  * GET /metrics is valid Prometheus text (histogram bucket
    monotonicity, label escaping);
  * work counters are deterministic across repeat runs;
  * the metrics_dump scraper works against a live webservice.
"""
import json
import time
import urllib.request

import pytest

from nebula_tpu.utils import trace
from nebula_tpu.utils.stats import (StatsManager, WorkCounters,
                                    current_work, use_work)


# ---- trace primitives -----------------------------------------------------


def test_span_is_noop_without_trace():
    assert trace.current_ctx() is None
    with trace.span("orphan") as rec:
        assert rec is None
    assert trace.wire_context() is None


def test_trace_nesting_and_store():
    store = trace.trace_store()
    with trace.start_trace("t-root", service="svc", tag="x") as tg:
        tid = tg.trace_id
        with trace.span("child-a"):
            with trace.span("grandchild"):
                pass
        with trace.span("child-b", k=1):
            pass
        trace.record_phase("phase", time.perf_counter() - 0.001, 0.001,
                           eb=4)
    entry = store.get(tid)
    assert entry is not None
    names = {s["name"] for s in entry["spans"]}
    assert names == {"t-root", "child-a", "grandchild", "child-b",
                     "phase"}
    by_name = {s["name"]: s for s in entry["spans"]}
    root = by_name["t-root"]
    assert root["psid"] == "" and root["attrs"]["tag"] == "x"
    assert by_name["child-a"]["psid"] == root["sid"]
    assert by_name["grandchild"]["psid"] == by_name["child-a"]["sid"]
    assert by_name["child-b"]["psid"] == root["sid"]
    assert by_name["phase"]["psid"] == root["sid"]
    tree = trace.render_tree(entry)
    assert tree.splitlines()[0].startswith("t-root")
    assert "    grandchild" in tree
    # after the trace closed, the thread has no context again
    assert trace.current_ctx() is None


def test_trace_ctx_cross_thread_isolated_parents():
    """use_ctx installs a per-thread COPY: concurrent spans share the
    sink but not the parent-slot (scheduler parallel branches)."""
    import threading
    with trace.start_trace("par", service="s") as tg:
        snap = trace.current_ctx()
        root_sid = snap.sid
        done = []

        def worker(i):
            with trace.use_ctx(snap):
                with trace.span(f"w{i}"):
                    pass
            done.append(i)

        ts = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert len(done) == 4
    entry = trace.trace_store().get(tg.trace_id)
    workers = [s for s in entry["spans"] if s["name"].startswith("w")]
    assert len(workers) == 4
    assert all(s["psid"] == root_sid for s in workers)


def test_trace_store_bounded():
    store = trace.TraceStore(capacity=3)
    for i in range(10):
        store.add(f"t{i}", f"n{i}", [])
    assert len(store.list(limit=50)) == 3
    assert store.get("t0") is None and store.get("t9") is not None


# ---- work counters --------------------------------------------------------


def test_work_counters_thread_local_and_dict():
    assert current_work() is None
    wc = WorkCounters()
    with use_work(wc):
        assert current_work() is wc
        current_work().add("edges_traversed", 5)
        current_work().add_rpc(100, 200)
        current_work().extend_frontier([1, 4])
    assert current_work() is None
    d = wc.as_dict()
    assert d == {"edges_traversed": 5, "frontier_sizes": [1, 4],
                 "rpc_calls": 1, "wire_bytes_sent": 100,
                 "wire_bytes_recv": 200, "device_dispatches": 0,
                 "storage_rows": 0}


def test_engine_query_attaches_work_and_trace():
    """Every statement produces a trace; SHOW TRACES lists it; device
    work counters land on the statement's ExecutionContext."""
    from nebula_tpu.exec.engine import QueryEngine
    from nebula_tpu.tpu.device import make_mesh
    from nebula_tpu.tpu.runtime import TpuRuntime

    eng = QueryEngine(tpu_runtime=TpuRuntime(make_mesh()))
    s = eng.new_session()
    for q in ["CREATE SPACE wk(partition_num=8, vid_type=INT64)",
              "USE wk", "CREATE EDGE e(w int)",
              "INSERT EDGE e(w) VALUES 1->2:(1), 2->3:(2), 1->3:(3)"]:
        r = eng.execute(s, q)
        assert r.error is None, f"{q} -> {r.error}"
    r = eng.execute(s, "GO 2 STEPS FROM 1 OVER e YIELD dst(edge) AS d")
    assert r.error is None
    r = eng.execute(s, "SHOW TRACES")
    assert r.error is None
    names = [row[1] for row in r.data.rows]
    assert "query:Go" in names
    tid = next(row[0] for row in r.data.rows if row[1] == "query:Go")
    entry = trace.trace_store().get(tid)
    span_names = {sp["name"] for sp in entry["spans"]}
    assert any(n.startswith("exec:") for n in span_names)
    # device phases present when the GO fused onto the device plane
    assert {"device:put", "device:dispatch", "device:fetch"} <= span_names


def test_device_work_counters_deterministic():
    """Two identical post-warmup runs produce byte-identical work
    counters (the bench regression signal)."""
    from nebula_tpu.exec.engine import QueryEngine
    from nebula_tpu.tpu.device import make_mesh
    from nebula_tpu.tpu.runtime import TpuRuntime

    rt = TpuRuntime(make_mesh())
    eng = QueryEngine(tpu_runtime=rt)
    s = eng.new_session()
    for q in ["CREATE SPACE dwk(partition_num=8, vid_type=INT64)",
              "USE dwk", "CREATE EDGE e(w int)",
              "INSERT EDGE e(w) VALUES 1->2:(1), 2->3:(2), 1->3:(3), "
              "3->4:(4), 2->4:(5)"]:
        assert eng.execute(s, q).error is None

    def probe():
        wc = WorkCounters()
        with use_work(wc):
            rows, st = rt.traverse(eng.store, "dwk", [1], ["e"], "out", 2)
        return wc.as_dict()

    probe()                      # warmup: escalation settles buckets
    w1, w2 = probe(), probe()
    assert json.dumps(w1) == json.dumps(w2)
    assert w1["edges_traversed"] > 0
    assert w1["frontier_sizes"][0] == 1      # the single seed
    assert w1["device_dispatches"] >= 1


# ---- Prometheus exposition ------------------------------------------------


def test_prometheus_histogram_monotone_and_escaping():
    sm = StatsManager()
    sm.inc("plain_total", 3)
    sm.inc_labeled("ops_total", {"op": 'quo"te\\back\nline'}, 2)
    sm.gauge("hbm_bytes", 12.5)
    for v in (50, 700, 700, 99_000, 2_000_000_000):
        sm.observe("lat_us", v, {"op": "go"})
    text = sm.to_prometheus()
    lines = text.splitlines()
    assert "# TYPE plain_total counter" in lines
    assert "plain_total 3" in lines
    # label escaping per the exposition format
    assert 'ops_total{op="quo\\"te\\\\back\\nline"} 2' in lines
    assert "hbm_bytes 12.5" in lines
    # histogram: cumulative buckets ending at +Inf == count
    buckets = [ln for ln in lines if ln.startswith("lat_us_bucket")]
    vals = [int(ln.rsplit(" ", 1)[1]) for ln in buckets]
    assert vals == sorted(vals), "bucket counts must be cumulative"
    assert 'le="+Inf"' in buckets[-1]
    assert vals[-1] == 5
    assert 'lat_us_count{op="go"} 5' in lines
    # the 2e9 observation only lands in +Inf
    assert vals[-1] - vals[-2] == 1


def test_metrics_endpoint_serves_prometheus():
    from nebula_tpu.cluster.webservice import WebService
    from nebula_tpu.utils.stats import stats

    stats().observe("ws_scrape_lat_us", 1234, {"op": "x"})
    stats().inc("ws_scrape_counter", 9)
    ws = WebService(role="graphd")
    ws.start()
    try:
        body = urllib.request.urlopen(
            f"http://{ws.addr}/metrics").read().decode()
        assert "# TYPE ws_scrape_counter counter" in body
        assert "ws_scrape_counter 9" in body
        assert 'ws_scrape_lat_us_bucket{op="x",le="5000"} 1' in body
        assert 'ws_scrape_lat_us_bucket{op="x",le="+Inf"} 1' in body
    finally:
        ws.stop()


# ---- the cluster acceptance test -----------------------------------------


@pytest.fixture(scope="module")
def traced_cluster(tmp_path_factory):
    """LocalCluster with a device runtime + one GO query already run."""
    from nebula_tpu.cluster.launcher import LocalCluster
    from nebula_tpu.tpu.device import make_mesh
    from nebula_tpu.tpu.runtime import TpuRuntime

    rt = TpuRuntime(make_mesh())
    c = LocalCluster(n_meta=1, n_storage=2, n_graph=1,
                     data_dir=str(tmp_path_factory.mktemp("traced")),
                     tpu_runtime=rt)
    try:
        cl = c.client()
        r = cl.execute("CREATE SPACE tr(partition_num=8, "
                       "vid_type=INT64)")
        assert r.error is None, r.error
        c.reconcile_storage()
        for q in ["USE tr", "CREATE TAG P(a int)", "CREATE EDGE E(w int)",
                  "INSERT VERTEX P(a) VALUES 1:(1), 2:(2), 3:(3)",
                  "INSERT EDGE E(w) VALUES 1->2:(5), 2->3:(7)"]:
            r = cl.execute(q)
            assert r.error is None, f"{q} -> {r.error}"
        r = cl.execute("GO 2 STEPS FROM 1 OVER E YIELD dst(edge) AS d")
        assert r.error is None, r.error
        assert sorted(x[0] for x in r.data.rows) == [3]
        yield c, cl
    finally:
        c.stop()


def _go_trace_entry():
    for t in trace.trace_store().list():
        if t["name"] == "query:Go":
            return trace.trace_store().get(t["tid"])
    raise AssertionError("no query:Go trace recorded")


def test_cluster_trace_stitches_services_and_device(traced_cluster):
    """ONE trace id covers graphd executors, storaged spans delivered
    over the RPC envelope, and the device put/dispatch/fetch phases."""
    entry = _go_trace_entry()
    spans = entry["spans"]
    # single trace: every span carries the same tid
    assert {s["tid"] for s in spans} == {entry["tid"]}
    names = {s["name"] for s in spans}
    # graphd-side executor spans
    assert any(n.startswith("exec:") for n in names)
    # storaged-side spans, shipped back over the RPC envelope
    remote_storaged = [s for s in spans
                      if s.get("svc") == "storaged" and s.get("remote")]
    assert remote_storaged, "no storaged span came back in a reply"
    # the remote span's parent chain reaches this trace's spans
    by_id = {s["sid"]: s for s in spans}
    assert any(s["psid"] in by_id for s in remote_storaged), \
        "remote spans are not stitched into the tree"
    # device-plane phase spans (the GO fused to TpuTraverse)
    assert {"device:put", "device:dispatch", "device:fetch"} <= names, \
        sorted(names)
    # the rendered tree nests a storaged span under a graphd rpc span
    tree = trace.render_tree(entry)
    assert "rpc.server:storage.get_neighbors (storaged [remote])" \
        in tree or "storaged" in tree


def test_cluster_insert_trace_has_raft_span(traced_cluster):
    """Write path: the storaged-side raft propose span rides back too
    (group commit renamed it raft:propose_batch; the `entries` attr
    carries the batch size)."""
    for t in trace.trace_store().list():
        if t["name"] in ("query:Insert", "query:InsertEdge",
                         "query:InsertVertex", "query:InsertEdges",
                         "query:InsertVertices"):
            entry = trace.trace_store().get(t["tid"])
            for s in entry["spans"]:
                if s["name"] == "raft:propose_batch":
                    assert s.get("attrs", {}).get("entries", 0) >= 1
                    return
    raise AssertionError(
        "no insert trace carries a raft:propose_batch span")


def test_traces_endpoint_and_metrics_dump(traced_cluster, capsys):
    """GET /traces serves the stitched trace; the metrics_dump scraper
    renders it and the /metrics text from a live webservice."""
    from nebula_tpu.cluster.webservice import WebService
    from nebula_tpu.tools import metrics_dump

    ws = WebService(role="graphd")
    ws.start()
    try:
        listing = json.loads(urllib.request.urlopen(
            f"http://{ws.addr}/traces").read())
        go = next(t for t in listing if t["name"] == "query:Go")
        full = json.loads(urllib.request.urlopen(
            f"http://{ws.addr}/traces?id={go['tid']}").read())
        assert full["tid"] == go["tid"] and full["spans"]
        txt = urllib.request.urlopen(
            f"http://{ws.addr}/traces?id={go['tid']}&format=text"
        ).read().decode()
        assert txt.startswith("query:Go")
        # the scraper CLI against the same endpoint
        assert metrics_dump.main(["--addr", ws.addr, "--traces"]) == 0
        assert go["tid"] in capsys.readouterr().out
        assert metrics_dump.main(
            ["--addr", ws.addr, "--trace", go["tid"]]) == 0
        assert "query:Go" in capsys.readouterr().out
        assert metrics_dump.main(
            ["--addr", ws.addr, "--grep", "num_queries"]) == 0
        assert "num_queries" in capsys.readouterr().out
    finally:
        ws.stop()


def test_cluster_query_work_counters(traced_cluster):
    """Cluster host-path work counters: RPC calls and wire bytes are
    counted and deterministic across identical repeat queries."""
    c, cl = traced_cluster
    eng = c.graphds[0].engine
    sess = eng.new_session()
    from nebula_tpu.utils.config import get_config
    get_config().set_dynamic("tpu_enable", False)   # force host path
    # tracing off: span payloads in RPC replies carry timing digits,
    # which would make wire-byte counts vary run-to-run (this is the
    # documented regression-probe mode; docs/OBSERVABILITY.md)
    get_config().set_dynamic("enable_query_tracing", False)
    try:
        def probe():
            wc = WorkCounters()
            with use_work(wc):
                r = eng.execute(sess, "USE tr")
                assert r.error is None
                r = eng.execute(sess,
                                "GO 2 STEPS FROM 1 OVER E "
                                "YIELD dst(edge) AS d")
                assert r.error is None, r.error
            return wc.as_dict()

        w1, w2 = probe(), probe()
    finally:
        get_config().dynamic_layer.pop("tpu_enable", None)
        get_config().dynamic_layer.pop("enable_query_tracing", None)
    assert w1["rpc_calls"] > 0 and w1["wire_bytes_sent"] > 0
    assert w1["edges_traversed"] >= 2      # 1->2, 2->3
    assert json.dumps(w1) == json.dumps(w2)


# ---- the statement phase ledger (ISSUE 24) --------------------------------


def _span(sid, psid, name, start_us, dur_us, **extra):
    return dict({"tid": "t", "sid": sid, "psid": psid, "name": name,
                 "svc": "graphd", "t0": (1_700_000_000_000_000 + start_us)
                 / 1e6, "dur_us": dur_us}, **extra)


def _latest(name):
    for t in trace.trace_store().list():
        if t["name"] == name:
            return trace.trace_store().get(t["tid"])
    raise AssertionError(f"no {name} trace recorded")


def _assert_budget_closes(entry):
    spans = entry["spans"]
    root = next(s for s in spans if not s["psid"])
    selfs = trace.self_times(spans)
    local = [s for s in spans if not s.get("remote")]
    assert set(selfs) == {s["sid"] for s in local}
    assert all(v >= 0 for v in selfs.values())
    assert abs(sum(selfs.values()) - root["dur_us"]) <= len(local), \
        (sum(selfs.values()), root["dur_us"])
    us, n = trace.fold_phases(spans)
    assert set(us) <= set(trace.PHASES) and set(n) <= set(trace.PHASES)
    assert abs(sum(us.values()) - root["dur_us"]) <= len(trace.PHASES)


def test_self_time_subtracts_the_union_not_the_sum():
    """Three overlapping children (a fan-out): the parent's self time
    is its length minus the UNION of theirs, the overlapped wall time is
    shared between the children open in it, and the whole closes."""
    spans = [_span("r", "", "query:T", 0, 100),
             _span("a", "r", "rpc:x", 10, 40),     # [10, 50)
             _span("b", "r", "rpc:x", 30, 40),     # [30, 70)
             _span("c", "r", "rpc:x", 60, 30),     # [60, 90)
             # a remote handler span: another clock, never in the sweep
             _span("h", "a", "rpc.server:x", 999, 20, remote=True)]
    selfs = trace.self_times(spans)
    assert "h" not in selfs
    assert selfs["r"] == pytest.approx(20.0)       # 100 - |[10, 90)|
    # the children's 110 us of lengths share the 80 us they cover, in
    # proportion: the sum (not each interval's own placement) closes
    assert selfs["a"] == pytest.approx(40 * 80 / 110)
    assert selfs["b"] == pytest.approx(40 * 80 / 110)
    assert selfs["c"] == pytest.approx(30 * 80 / 110)
    assert sum(selfs.values()) == pytest.approx(100.0)
    us, n = trace.fold_phases(spans)
    # the handler's LENGTH (20 of a's 40 us) places half of a's self
    # time under `remote`; rpc_wait counts the three rpc: spans
    assert us == {"other": 20, "rpc_wait": 65, "remote": 15}
    assert n == {"other": 1, "rpc_wait": 3, "remote": 1}
    # nested: a fan-out's scale carries down to the grandchildren
    spans += [_span("a1", "a", "store:x", 10, 20),
              _span("b1", "b", "store:x", 30, 40)]
    selfs = trace.self_times(spans)
    assert selfs["a"] == pytest.approx(20 * 80 / 110)
    assert selfs["a1"] == pytest.approx(20 * 80 / 110)
    assert selfs["b"] == 0 and selfs["b1"] == pytest.approx(40 * 80 / 110)
    assert sum(selfs.values()) == pytest.approx(100.0)


def test_self_time_clips_children_and_adopts_orphans():
    spans = [_span("r", "", "query:T", 0, 100),
             _span("e", "r", "exec:X", 10, 50),            # [10, 60)
             _span("late", "e", "device:fetch", 50, 30),   # clipped to 60
             _span("lost", "gone", "store:y", 70, 10),     # parent unknown
             _span("m", "e", "rpc:retry", 20, 0)]          # a marker
    selfs = trace.self_times(spans)
    assert selfs == {"r": 40.0, "e": 40.0, "late": 10.0, "lost": 10.0,
                     "m": 0.0}
    us, n = trace.fold_phases(spans)
    assert us == {"other": 40, "exec": 50, "fetch": 10}
    assert "rpc_wait" not in n                 # the marker is no RPC


def test_span_times_come_from_one_clock():
    """Start and length are readings of one monotonic clock: a child
    lies inside its parent to the microsecond, and `t0` is epoch time."""
    t_wall = time.time()
    with trace.start_trace("clock", service="s") as tg:
        t_in = time.perf_counter()
        with trace.span("outer"):
            with trace.span("inner"):
                time.sleep(0.002)
        trace.record_phase("timed", t_in, 0.001)
        trace.mark("marker", k=1)
    by = {s["name"]: s for s in trace.trace_store().get(tg.trace_id)["spans"]}

    def iv(s):
        a = round(s["t0"] * 1e6)
        return a, a + s["dur_us"]
    assert abs(by["clock"]["t0"] - t_wall) < 5.0
    assert iv(by["clock"])[0] <= iv(by["outer"])[0] <= iv(by["inner"])[0]
    assert iv(by["inner"])[1] <= iv(by["outer"])[1] + 1
    assert iv(by["outer"])[1] <= iv(by["clock"])[1] + 1
    assert by["inner"]["dur_us"] >= 2000
    assert by["timed"]["dur_us"] == 1000
    assert iv(by["clock"])[0] <= iv(by["timed"])[0] <= iv(by["outer"])[0]
    assert by["marker"]["dur_us"] == 0 and by["marker"]["attrs"] == {"k": 1}


def test_cluster_statement_budgets_close(traced_cluster):
    """For a GO, a MATCH and an INSERT through a LocalCluster the self
    times of the local spans sum to the root's duration (1 us a span),
    and the root now covers the session update after the executors."""
    c, cl = traced_cluster
    for q, name in [
            ("GO 2 STEPS FROM 1 OVER E YIELD dst(edge) AS d", "query:Go"),
            ("MATCH (a:P)-[e:E]->(b) WHERE id(a) == 1 RETURN id(b)",
             "query:Match"),
            ("INSERT EDGE E(w) VALUES 3->1:(9)", "query:InsertEdges")]:
        r = cl.execute(q)
        assert r.error is None, f"{q} -> {r.error}"
        entry = _latest(name)
        _assert_budget_closes(entry)
        names = [s["name"] for s in entry["spans"]]
        assert "rpc:meta.update_session" in names, names
        assert "graphd:encode" in names or r.data is None
    go = _latest("query:Go")
    probe = next(s for s in go["spans"]
                 if s["name"] == "tpu:snapshot_check")
    # one `storage.probe` request a storaged HOST (two here), not one a part
    fan = [s for s in go["spans"] if s["name"] == "storage:storage.probe"]
    assert len(fan) == 2 and {s["psid"] for s in fan} == {probe["sid"]}
    assert sum(s["attrs"]["parts"] for s in fan) == 8
    served = [s for s in go["spans"] if s["name"].startswith("rpc.server:")]
    assert served and all(s["attrs"]["inbox_us"] >= 0 for s in served)


def test_device_phases_are_real_intervals(traced_cluster):
    """device:queue/put/dispatch/fetch/materialise are disjoint, in
    order, and inside the executor span that drove the kernel: the
    launch's phases as children of its `tpu:launch`, the row assembly
    beside it."""
    entry = _latest("query:Go")
    by_id = {s["sid"]: s for s in entry["spans"]}
    order = ["device:queue", "device:put", "device:dispatch",
             "device:fetch", "device:materialise"]
    dev = sorted((s for s in entry["spans"] if s["name"] in order),
                 key=lambda s: s["t0"])
    assert [s["name"] for s in dev if s["name"] != "device:fetch"] == \
        [n for n in order if n != "device:fetch"]
    assert [s["name"] for s in dev][:4] == order[:4]
    launch = by_id[dev[0]["psid"]]
    ex = by_id[launch["psid"]]
    assert launch["name"] == "tpu:launch" and ex["name"].startswith("exec:")
    a0 = round(ex["t0"] * 1e6)
    end = a0
    for s in dev:
        assert s["psid"] == (ex if s["name"] == "device:materialise"
                             else launch)["sid"]
        a = round(s["t0"] * 1e6)
        assert a >= end, (s["name"], a, end)        # disjoint, ordered
        end = a + s["dur_us"]
    assert end <= a0 + ex["dur_us"] + 1
    assert next(s for s in dev if s["name"] == "device:dispatch")[
        "attrs"]["attempt"] == 0


def test_root_covers_parse_and_carries_plan_cache(traced_cluster):
    c, cl = traced_cluster
    q = "GO 1 STEPS FROM 2 OVER E YIELD dst(edge) AS d, E.w AS w"
    assert cl.execute(q).error is None
    miss = _latest("query:Go")
    root = next(s for s in miss["spans"] if not s["psid"])
    assert root["attrs"]["plan_cache"] == "miss"
    kids = {s["name"] for s in miss["spans"] if s["psid"] == root["sid"]}
    assert {"graphd:parse", "graphd:plan", "graphd:encode"} <= kids, kids
    assert cl.execute(q).error is None
    hit = _latest("query:Go")
    assert hit["tid"] != miss["tid"]
    root = next(s for s in hit["spans"] if not s["psid"])
    assert root["attrs"]["plan_cache"] == "hit"
    names = {s["name"] for s in hit["spans"]}
    assert not names & {"graphd:parse", "graphd:plan"}
    _assert_budget_closes(hit)


def test_compound_statement_is_one_trace():
    """`a; b` is ONE trace, `query:Seq`, whose root covers the one
    parse and both sub-statements (before ISSUE 24 each sub-statement
    opened a trace of its own and the parse had none)."""
    from nebula_tpu.exec.engine import QueryEngine
    eng = QueryEngine()
    s = eng.new_session()
    n0 = len(trace.trace_store().list(limit=1000))
    r = eng.execute(s, "YIELD 1 AS a; YIELD 2 AS b")
    assert r.error is None and r.data.rows == [[2]]
    assert len(trace.trace_store().list(limit=1000)) in (n0 + 1, 256)
    entry = _latest("query:Seq")
    names = [sp["name"] for sp in entry["spans"]]
    assert names.count("graphd:parse") == 1
    assert names.count("graphd:plan") == 2
    _assert_budget_closes(entry)
    # an unparseable statement is a trace too, with its parse span
    assert eng.execute(s, "GOGO").error.startswith("SyntaxError")
    assert "graphd:parse" in [sp["name"]
                              for sp in _latest("query:Parse")["spans"]]


def _engine_with_edges(space):
    from nebula_tpu.exec.engine import QueryEngine
    from nebula_tpu.tpu.device import make_mesh
    from nebula_tpu.tpu.runtime import TpuRuntime
    eng = QueryEngine(tpu_runtime=TpuRuntime(make_mesh()))
    s = eng.new_session()
    for q in [f"CREATE SPACE {space}(partition_num=8, vid_type=INT64)",
              f"USE {space}", "CREATE EDGE e(w int)",
              "INSERT EDGE e(w) VALUES 1->2:(1), 2->3:(2), 1->3:(3)"]:
        r = eng.execute(s, q)
        assert r.error is None, f"{q} -> {r.error}"
    return eng, s


def test_program_spans_are_on_the_profilers_clock(tmp_path):
    """Inside ANY jax.profiler session the program's spans are events on
    a /host:CPU thread line, within the caller's own annotation (this
    replaces the per-dispatch profiler-directory traces, which nothing
    read)."""
    import glob

    import jax
    from jax.profiler import ProfileData
    eng, s = _engine_with_edges("pf")
    go = "GO 2 STEPS FROM 1 OVER e YIELD dst(edge) AS d"
    assert eng.execute(s, go).error is None          # warm: compile
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("test:stmt"):
            assert eng.execute(s, go).error is None
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    assert found, "the profiler session left no xplane"
    host = next(p for p in ProfileData.from_file(found[-1]).planes
                if p.name == "/host:CPU")
    for ln in host.lines:
        evs, stats_of = {}, {}
        for e in ln.events:
            evs.setdefault(e.name, []).append(
                (e.start_ns, e.start_ns + e.duration_ns))
            stats_of[e.name] = list(e.stats)
        if "test:stmt" in evs:
            break
    else:
        raise AssertionError("the caller's annotation is on no host line")
    a, b = evs["test:stmt"][0]
    # the root's annotation opens before the parse names the statement
    assert ("name", "query:Go") in stats_of["query:Statement"]
    for name in ("query:Statement", "exec:TpuTraverse", "tpu:snapshot_check",
                 "device:put", "device:dispatch", "device:fetch",
                 "device:materialise", "graphd:plan"):
        if name == "graphd:plan":           # a plan-cache hit: no plan
            assert name not in evs
            continue
        assert name in evs, (name, sorted(evs))
        assert all(a <= x and y <= b for x, y in evs[name]), name


def test_phase_vocabulary_is_fixed_after_a_mixed_run(traced_cluster):
    from nebula_tpu.utils.stats import stats
    c, cl = traced_cluster
    for q in ["SHOW SPACES", "GOGO", "YIELD 1", "FETCH PROP ON P 1 YIELD P.a",
              "FIND SHORTEST PATH FROM 1 TO 3 OVER E YIELD path AS p",
              "GET SUBGRAPH 1 STEPS FROM 1 YIELD VERTICES AS v",
              "INSERT VERTEX P(a) VALUES 9:(9); YIELD 2"]:
        cl.execute(q)
    snap = stats().snapshot()
    for name in (trace.PHASE_US, trace.PHASE_N):
        labels = {k for k in snap if k.startswith(name + "{")}
        assert labels and len(labels) <= 20
        assert labels <= {f"{name}{{phase={p}}}" for p in trace.PHASES}
    assert len(trace.PHASES) <= 20
    assert snap["stmt_phase_n{phase=other}"] >= 7     # one per statement
    text = stats().to_prometheus()
    assert 'stmt_phase_us{phase="rpc_wait"}' in text
    assert snap["process_cpu_s"] > 0


def test_tracing_off_moves_no_phase_counter_but_device_series_do():
    from nebula_tpu.utils.config import get_config
    from nebula_tpu.utils.stats import stats
    eng, s = _engine_with_edges("off")
    go = "GO 2 STEPS FROM 1 OVER e YIELD dst(edge) AS d"
    assert eng.execute(s, go).error is None

    def read():
        snap = stats().snapshot()
        return ({k: v for k, v in snap.items()
                 if k.startswith("stmt_phase_")},
                {k: snap.get(f"{k}.count", 0) for k in
                 ("tpu_put_s", "tpu_fetch_s", "tpu_mat_s", "tpu_queue_s",
                  "tpu_kernel_s")},
                snap["tpu_escalation_retries"], snap["tpu_refetches"])
    get_config().set_dynamic("enable_query_tracing", False)
    try:
        n0 = len(trace.trace_store().list(limit=1000))
        p0, d0, _, _ = read()
        for _ in range(2):
            assert eng.execute(s, go).error is None
        p1, d1, retries, refetches = read()
        assert len(trace.trace_store().list(limit=1000)) == n0
    finally:
        get_config().dynamic_layer.pop("enable_query_tracing", None)
    assert p1 == p0 and p0
    assert all(d1[k] == d0[k] + 2 for k in d0), (d0, d1)
    assert retries >= 0 and refetches >= 0
    assert eng.execute(s, go).error is None
    assert read()[0] != p0
