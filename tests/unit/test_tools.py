"""Tool entrypoints: ldbc_import, db_dump, csr_dump, storage_perf —
each driven through its main() like a user would."""
import pytest

from nebula_tpu.tools import csr_dump, db_dump, ldbc_import, storage_perf


@pytest.fixture()
def csvs(tmp_path):
    people = tmp_path / "person.csv"
    people.write_text("id|name|age\n1|ann|30\n2|bob|25\n3|cid|41\n")
    knows = tmp_path / "knows.csv"
    knows.write_text("src|dst|since\n1|2|2010\n2|3|2015\n1|3|2012\n")
    return people, knows


def test_ldbc_import_and_dumps(tmp_path, csvs, capsys):
    people, knows = csvs
    cp = tmp_path / "cp"
    rc = ldbc_import.main([
        "--space", "ld", "--parts", "4", "--vid-type", "INT64",
        "--vertices", f"Person:{people}:id,name:string,age:int",
        "--edges", f"KNOWS:{knows}:src,dst,since:int",
        "--delimiter", "|", "--checkpoint", str(cp)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "3 vertices" in out and "3 edges" in out

    # restored checkpoint serves queries
    from nebula_tpu.exec import QueryEngine
    from nebula_tpu.graphstore.store import GraphStore
    st = GraphStore.from_checkpoint(str(cp))
    eng = QueryEngine(st)
    s = eng.new_session()
    eng.execute(s, "USE ld")
    r = eng.execute(s, "GO FROM 1 OVER KNOWS YIELD dst(edge), KNOWS.since")
    assert r.ok and sorted(map(tuple, r.data.rows)) == [(2, 2010), (3, 2012)]

    # db_dump over the checkpoint
    assert db_dump.main([str(cp)]) == 0
    out = capsys.readouterr().out
    assert "vertices=3" in out and "edges=3" in out
    assert db_dump.main([str(cp), "--mode", "edge", "--limit", "2"]) == 0
    out = capsys.readouterr().out
    assert "-[:KNOWS@0]->" in out

    # csr_dump over the checkpoint
    assert csr_dump.main([str(cp), "--space", "ld"]) == 0
    out = capsys.readouterr().out
    assert "block (KNOWS, out): edges=3" in out
    assert "tag table Person: present=3" in out


def test_ldbc_import_string_vids(tmp_path, capsys):
    pf = tmp_path / "v.csv"
    pf.write_text("id,score\na,1.5\nb,2.5\n")
    ef = tmp_path / "e.csv"
    ef.write_text("src,dst\na,b\n")
    rc = ldbc_import.main([
        "--space", "lds", "--parts", "2",
        "--vid-type", "FIXED_STRING(32)",
        "--vertices", f"T:{pf}:id,score:float",
        "--edges", f"E:{ef}:src,dst"])
    assert rc == 0
    assert "2 vertices" in capsys.readouterr().out


def test_storage_perf_smoke(capsys):
    rc = storage_perf.main(["--vertices", "50", "--edges", "100",
                            "--reads", "40", "--batch", "20"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "getNeighbors" in out and "op/s" in out


def test_metrics_dump_cluster_scrape(capsys):
    """--addrs scrapes every host, prints per-host sections and a
    merged (counters summed) view (ISSUE 8 satellite)."""
    from nebula_tpu.cluster.webservice import WebService
    from nebula_tpu.tools import metrics_dump
    from nebula_tpu.utils.stats import stats

    stats().inc("md_cluster_probe", 3)
    ws1 = WebService(role="graphd")
    ws2 = WebService(role="storaged")
    ws1.start()
    ws2.start()
    try:
        rc = metrics_dump.main(
            ["--addrs", f"{ws1.addr},{ws2.addr}",
             "--grep", "md_cluster_probe"])
        assert rc == 0
        out = capsys.readouterr().out
        assert f"== {ws1.addr}" in out and f"== {ws2.addr}" in out
        # both webservices front the same in-process registry, so the
        # merged view sums the sample across hosts: 3 + 3
        assert "== merged (2/2 hosts)" in out
        assert "md_cluster_probe 6" in out
    finally:
        ws1.stop()
        ws2.stop()


def test_metrics_dump_watch_deltas(capsys):
    from nebula_tpu.cluster.webservice import WebService
    from nebula_tpu.tools import metrics_dump
    from nebula_tpu.utils.stats import stats

    ws = WebService(role="graphd")
    ws.start()
    try:
        import threading

        def bump():
            stats().inc("md_watch_probe", 5)
        t = threading.Timer(0.1, bump)
        t.start()
        rc = metrics_dump.main(["--addrs", ws.addr, "--watch", "0.3",
                                "--iterations", "1",
                                "--grep", "md_watch_probe"])
        t.join()
        assert rc == 0
        out = capsys.readouterr().out
        assert "baseline:" in out
        assert "md_watch_probe" in out and "(+5)" in out
    finally:
        ws.stop()


def test_metrics_dump_shards_view(capsys):
    """--shards (ISSUE 17): per-device HBM ledger rows, the
    ledger-vs-pinned sum check and exchange bytes, scraped from the
    prometheus exposition (quoted label values)."""
    from nebula_tpu.cluster.webservice import WebService
    from nebula_tpu.tools import metrics_dump
    from nebula_tpu.utils.stats import stats

    st = stats()
    with st.lock:
        # earlier sharded-runtime tests leave their own ledger rows in
        # the process-global registry — start from a clean ledger
        st.labeled_gauges.pop("tpu_shard_hbm_bytes", None)
    st.gauge("tpu_shards", 4.0)
    for p in range(4):
        st.gauge_labeled("tpu_shard_hbm_bytes", {"shard": p},
                         float(1000 + p))
    st.gauge("tpu_hbm_bytes_pinned", float(sum(
        1000 + p for p in range(4))))
    st.inc("tpu_all_to_all_bytes", 2048)
    a2a_total = int(st.snapshot().get("tpu_all_to_all_bytes", 0))
    ws = WebService(role="graphd")
    ws.start()
    try:
        rc = metrics_dump.main(["--addr", ws.addr, "--shards"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "mesh width: 4 shard(s)" in out
        assert "shard 0" in out and "shard 3" in out
        assert "hbm=1003" in out
        assert "-> OK" in out and "MISMATCH" not in out
        assert f"all_to_all exchanged: {a2a_total} bytes" in out

        # a stale pinned total is called out, not silently summed over
        st.gauge("tpu_hbm_bytes_pinned", 1.0)
        rc = metrics_dump.main(["--addr", ws.addr, "--shards"])
        assert rc == 0
        assert "MISMATCH" in capsys.readouterr().out
    finally:
        ws.stop()
        st.gauge("tpu_hbm_bytes_pinned", 0.0)


def test_metrics_dump_fleet_view(capsys):
    """--fleet (ISSUE 20): per-coordinator session gauge, goodput
    ledger by statement kind, epoch-propagation lag mean and the
    failover-plane counters, scraped from the prometheus exposition."""
    from nebula_tpu.cluster.webservice import WebService
    from nebula_tpu.tools import metrics_dump
    from nebula_tpu.utils.stats import stats

    st = stats()
    with st.lock:
        # earlier engine/epoch tests leave observations in the
        # process-global registry — start from known totals
        st.histograms.pop("query_latency_us_hist", None)
        st.histograms.pop("epoch_propagation_lag_ms", None)
        st.labeled.pop("overload_server_rejections", None)
        st.counters["cluster_epoch_folds"] = 3
        st.counters["session_moves"] = 2
        st.counters["coordinator_failovers"] = 1
        st.counters["graphd_drains"] = 0
        st.counters["kill_owner_dead"] = 0
    st.gauge("graph_sessions", 7.0)
    for _ in range(3):
        st.observe("query_latency_us_hist", 900.0, {"kind": "go"})
    st.observe("query_latency_us_hist", 4000.0, {"kind": "match"})
    st.observe("epoch_propagation_lag_ms", 4.0)
    st.observe("epoch_propagation_lag_ms", 8.0)
    st.inc_labeled("overload_server_rejections",
                   {"op": "graph.statement_capacity", "role": "graphd"},
                   4)
    ws = WebService(role="graphd")
    ws.start()
    try:
        rc = metrics_dump.main(["--addr", ws.addr, "--fleet"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "fleet samples" in out
        assert "sessions: 7" in out
        assert "statements served: 4" in out
        assert "go=3" in out and "match=1" in out
        assert "epoch folds: 3" in out
        assert "propagation lag: 6.00ms mean of 2" in out
        assert "session moves: 2" in out and "failovers: 1" in out
        assert "capacity sheds: 4" in out
    finally:
        ws.stop()
        st.gauge("graph_sessions", 0.0)


def test_metrics_dump_perfetto_export(tmp_path, capsys):
    """--perfetto exports scraped trace trees + stall captures as
    Chrome trace-event JSON (ISSUE 9 satellite): one process track per
    daemon, one thread track per service, device spans included,
    stalls as instant events."""
    import json

    from nebula_tpu.cluster.webservice import WebService
    from nebula_tpu.tools import metrics_dump
    from nebula_tpu.utils import trace
    from nebula_tpu.utils.workload import stall_watchdog

    # a stitched trace with host + device + remote-ish spans
    with trace.start_trace("query:Go", service="graphd", stmt="GO ..."):
        with trace.span("exec:ExpandAll", node=7):
            with trace.span("device:dispatch", eb=[256]):
                pass
        trace.graft([{"tid": "t1", "sid": "r1", "psid": "x",
                      "name": "store:get_neighbors", "svc": "storaged",
                      "t0": 1.0, "dur_us": 42}])
    stall_watchdog().clear()
    stall_watchdog()._capture(
        "dispatch", {"kernel": "traverse", "state": "queued"},
        1.5, 0.5)
    ws = WebService(role="graphd")
    ws.start()
    out_path = tmp_path / "cluster.trace.json"
    try:
        rc = metrics_dump.main(["--addr", ws.addr,
                                "--perfetto", str(out_path)])
        assert rc == 0
        doc = json.loads(out_path.read_text())
        evs = doc["traceEvents"]
        spans = [e for e in evs if e["ph"] == "X"]
        names = {e["name"] for e in spans}
        assert {"query:Go", "exec:ExpandAll",
                "device:dispatch"} <= names
        # remote span rides on its own service track
        remote = next(e for e in spans
                      if e["name"] == "store:get_neighbors")
        assert "[remote]" in remote["cat"]
        for e in spans:
            assert e["pid"] and e["tid"] and "ts" in e and "dur" in e
        # process/thread metadata names the tracks
        meta = {e["name"] for e in evs if e["ph"] == "M"}
        assert {"process_name", "thread_name"} <= meta
        # the stall capture lands as a global instant event
        stall = next(e for e in evs if e["ph"] == "i")
        assert stall["name"] == "stall:dispatch"
        assert stall["args"]["subject"]["kernel"] == "traverse"
        # --stalls lists the capture too
        rc = metrics_dump.main(["--addr", ws.addr, "--stalls"])
        assert rc == 0
        assert "dispatch" in capsys.readouterr().out
    finally:
        ws.stop()
        stall_watchdog().clear()


def test_metrics_dump_queries_listing(capsys):
    """--queries prints the live workload rows from GET /queries."""
    from nebula_tpu.cluster.webservice import WebService
    from nebula_tpu.tools import metrics_dump
    from nebula_tpu.utils.workload import live_registry

    lq = live_registry().register(
        qid=990001, session=7, user="root",
        stmt="GO FROM 1 OVER E", kind="Go")
    assert lq is not None
    lq.node_start("ExpandAll", 3)
    ws = WebService(role="graphd")
    ws.start()
    try:
        rc = metrics_dump.main(["--addr", ws.addr, "--queries"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "q990001" in out and "ExpandAll#3" in out
    finally:
        ws.stop()
        live_registry().deregister(990001)


def test_metrics_dump_unreachable_host(capsys):
    """In cluster mode a dead host is reported and skipped — the rest
    of the scrape still merges (single-addr mode stays fatal)."""
    from nebula_tpu.cluster.webservice import WebService
    from nebula_tpu.tools import metrics_dump

    ws = WebService(role="graphd")
    ws.start()
    try:
        rc = metrics_dump.main(["--addrs", f"127.0.0.1:1,{ws.addr}"])
        assert rc == 0
        cap = capsys.readouterr()
        assert "scrape of 127.0.0.1:1 failed" in cap.err
        assert "== merged (1/2 hosts)" in cap.out
    finally:
        ws.stop()


def test_meta_dump_data_dir(tmp_path, capsys):
    from nebula_tpu.exec import QueryEngine
    from nebula_tpu.graphstore.store import GraphStore
    from nebula_tpu.tools import meta_dump

    st = GraphStore(data_dir=str(tmp_path))
    e = QueryEngine(st)
    s = e.new_session()
    for q in ['CREATE SPACE md(partition_num=2, vid_type=INT64)', 'USE md',
              'CREATE TAG t(name string)', 'CREATE EDGE e(w int)',
              'CREATE TAG INDEX i_n ON t(name)',
              'CREATE FULLTEXT TAG INDEX ft_n ON t(name)',
              'ADD LISTENER ELASTICSEARCH "127.0.0.1:9200"',
              'CREATE USER reader WITH PASSWORD "x"',
              'GRANT ROLE USER ON md TO reader']:
        r = e.execute(s, q)
        assert r.ok, f"{q} -> {r.error}"
    st.close()

    assert meta_dump.main(["--data-dir", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    for needle in ["space `md'", "tag t v", "edge e v", "tag index i_n",
                   "fulltext tag index ft_n", "listener ELASTICSEARCH",
                   "user `reader'", "md:USER"]:
        assert needle in out, (needle, out)


def test_meta_dump_live_cluster(tmp_path, capsys):
    from nebula_tpu.cluster.launcher import LocalCluster
    from nebula_tpu.tools import meta_dump

    c = LocalCluster(n_meta=1, n_storage=2, n_graph=1,
                     data_dir=str(tmp_path))
    try:
        cl = c.client()
        assert cl.execute("CREATE SPACE lv(partition_num=4, "
                          "replica_factor=1, vid_type=INT64)").error is None
        c.reconcile_storage()
        assert cl.execute("USE lv").error is None
        assert cl.execute("CREATE TAG n(x int)").error is None
        assert meta_dump.main(["--addr", c.meta_addrs[0]]) == 0
        out = capsys.readouterr().out
        assert "space `lv'" in out and "tag n v" in out \
            and "part 0:" in out
    finally:
        c.stop()


def test_metrics_dump_deltas_view(capsys):
    """--deltas (ISSUE 19): per-shard delta fill rows, the
    repin-avoided share and compaction count, scraped from the
    prometheus exposition."""
    from nebula_tpu.cluster.webservice import WebService
    from nebula_tpu.tools import metrics_dump
    from nebula_tpu.utils.stats import stats

    st = stats()
    with st.lock:
        st.labeled_gauges.pop("tpu_shard_delta_edges", None)
    st.gauge("tpu_delta_edges", 30.0)
    st.gauge("tpu_delta_bytes", 4096.0)
    for p in range(4):
        st.gauge_labeled("tpu_shard_delta_edges", {"shard": p},
                         float(10 - p))
    pins0 = st.snapshot().get("tpu_pins", 0)
    avoided0 = st.snapshot().get("tpu_repin_avoided", 0)
    comps0 = st.snapshot().get("tpu_compactions", 0)
    st.inc("tpu_repin_avoided", 3)
    st.inc("tpu_compactions", 1)
    ws = WebService(role="graphd")
    ws.start()
    try:
        rc = metrics_dump.main(["--addr", ws.addr, "--deltas"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "delta plane: 30 rows, 4096 bytes" in out
        assert "shard 0" in out and "shard 3" in out
        assert "delta_rows=10" in out
        assert f"repins avoided: {int(avoided0) + 3} " \
               f"vs pins {int(pins0)}" in out
        assert f"compactions: {int(comps0) + 1}" in out
    finally:
        ws.stop()
        st.gauge("tpu_delta_edges", 0.0)
        st.gauge("tpu_delta_bytes", 0.0)
