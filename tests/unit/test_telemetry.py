"""Production telemetry plane (ISSUE 8): cluster-wide PROFILE cost
attribution, PROFILE parity + parallel schedule, PR5-path trace
coverage (retries / breaker transitions / dedup fast path), SLO burn
rates, metric federation, and the metric-catalogue lint."""
import json
import pathlib
import re
import time
import urllib.request

import pytest

from nebula_tpu.cluster.launcher import LocalCluster
from nebula_tpu.cluster.rpc import RpcClient, reset_breakers
from nebula_tpu.cluster.storage_client import StorageClient
from nebula_tpu.core.wire import to_wire
from nebula_tpu.exec.engine import QueryEngine
from nebula_tpu.utils import trace
from nebula_tpu.utils.config import get_config
from nebula_tpu.utils.failpoints import fail
from nebula_tpu.utils.stats import CostRecorder, stats, use_cost

REPO = pathlib.Path(__file__).resolve().parents[2]


@pytest.fixture()
def clean_faults():
    fail.reset()
    reset_breakers()
    yield
    fail.reset()
    reset_breakers()


@pytest.fixture(scope="module")
def cluster():
    c = LocalCluster(n_meta=1, n_storage=2, n_graph=1)
    client = c.client()

    def run(q, expect_ok=True):
        rs = client.execute(q)
        if expect_ok:
            assert rs.error is None, f"{q} -> {rs.error}"
        return rs

    run("CREATE SPACE tel(partition_num=4, replica_factor=2, "
        "vid_type=INT64)")
    c.reconcile_storage()
    run("USE tel")
    run("CREATE TAG Person(name string, age int)")
    run("CREATE EDGE KNOWS(w int)")
    run('INSERT VERTEX Person(name, age) VALUES '
        '1:("ann",30), 2:("bob",25), 3:("cid",41)')
    run("INSERT EDGE KNOWS(w) VALUES 1->2:(7), 1->3:(9), 2->3:(5)")
    c.run = run
    yield c
    c.stop()


# -- cost recorder unit surface ---------------------------------------------


def test_cost_recorder_merge_reply():
    cc = CostRecorder()
    cc.add("calls", 1)
    # "us" is the remote handler time in fixed-width decimal (reply
    # byte determinism); it maps to remote_us on merge
    cc.merge_reply({"us": "000001234", "rows": 10, "wal_fsyncs": 2})
    cc.merge_reply({"us": "000000766", "rows": 5})
    d = cc.as_dict()
    assert d["remote_us"] == 2000 and d["rows"] == 15
    assert d["wal_fsyncs"] == 2 and d["calls"] == 1
    assert bool(cc)


def test_cost_reply_envelope_fixed_width(cluster, clean_faults):
    """A cost-flagged request's reply carries a cost record whose `us`
    field is fixed-width — reply byte counts stay deterministic."""
    addr = cluster.storage_servers[0].addr
    cli = RpcClient.from_addr(addr)
    try:
        cc = CostRecorder()
        with use_cost(cc):
            cli.call("storage.part_stats", space="tel", part=0)
        d = cc.as_dict()
        assert d["calls"] == 1 and "remote_us" in d
        assert d["bytes_sent"] > 0 and d["bytes_recv"] > 0
    finally:
        cli.close()


# -- cluster-wide PROFILE ---------------------------------------------------


def test_profile_parity_cluster_rows_and_remote_cost(cluster,
                                                     clean_faults):
    """PROFILE returns byte-identical rows to the plain run AND its
    plan rows carry per-node remote cost (storaged µs / rows) from the
    reply envelopes — cluster-wide attribution, not graphd wall time."""
    q = "GO FROM 1 OVER KNOWS YIELD dst(edge) AS d, KNOWS.w AS w"
    plain = cluster.run(q)
    prof = cluster.run("PROFILE " + q)
    assert sorted(map(tuple, prof.data.rows)) == \
        sorted(map(tuple, plain.data.rows))
    assert prof.plan_desc and "rows=" in prof.plan_desc
    assert "remote={" in prof.plan_desc, prof.plan_desc
    assert "remote_us=" in prof.plan_desc
    assert "calls=" in prof.plan_desc


def test_profile_write_carries_wal_fsyncs(cluster, clean_faults):
    rs = cluster.run('PROFILE INSERT VERTEX Person(name, age) '
                     'VALUES 9:("zed",1)')
    assert "wal_fsyncs=" in rs.plan_desc, rs.plan_desc


def test_forwarded_cost_records_carry_no_variable_width_timing(
        cluster, clean_faults):
    """Reply-envelope cost records must contain NO variable-width
    timing ints: the only timing field on the wire is the fixed-width
    `us` string — nested-hop remote_us would otherwise make reply byte
    counts timing-dependent and flake the wire-byte regression gate."""
    addr = cluster.storage_servers[0].addr
    cli = RpcClient.from_addr(addr)
    try:
        cc = CostRecorder()
        # raw reply inspection: monkey-scope via the recorder is not
        # enough, we need the on-wire record itself
        seen = {}
        orig = CostRecorder.merge_reply

        def spy(self, cost):
            seen.update(cost)
            return orig(self, cost)

        CostRecorder.merge_reply = spy
        try:
            with use_cost(cc):
                cli.call("storage.part_stats", space="tel", part=0)
        finally:
            CostRecorder.merge_reply = orig
        assert seen, "no cost record came back"
        for k, v in seen.items():
            if k == "us":
                assert isinstance(v, str) and len(v) == 9, (k, v)
            else:
                assert not k.endswith("_us"), \
                    f"variable-width timing field {k} on the wire"
    finally:
        cli.close()


def test_profile_uses_parallel_schedule():
    """The old `profile is None` gate is gone: a branchy profiled plan
    dispatches on the parallel ready-queue (recorded by the
    scheduler_parallel_plans counter)."""
    eng = QueryEngine()
    s = eng.new_session()
    for q in ['CREATE SPACE par(partition_num=2, vid_type=FIXED_STRING(8))',
              'USE par', 'CREATE EDGE e(w int)',
              'INSERT EDGE e(w) VALUES "a"->"b":(1), "b"->"c":(2)']:
        r = eng.execute(s, q)
        assert r.error is None, f"{q} -> {r.error}"
    q = ('GO FROM "a" OVER e YIELD dst(edge) AS d '
         'UNION GO FROM "b" OVER e YIELD dst(edge) AS d')
    plain = eng.execute(s, q)
    assert plain.error is None
    before = stats().snapshot().get("scheduler_parallel_plans", 0)
    prof = eng.execute(s, "PROFILE " + q)
    assert prof.error is None
    after = stats().snapshot().get("scheduler_parallel_plans", 0)
    assert after > before, \
        "profiled run fell back to the sequential scheduler"
    assert sorted(map(tuple, prof.data.rows)) == \
        sorted(map(tuple, plain.data.rows))


# -- PR5-path trace coverage ------------------------------------------------


def _spans_of(tid):
    entry = trace.trace_store().get(tid)
    assert entry is not None
    return entry["spans"]


def test_retry_attempts_traced_with_peer(clean_faults):
    """Every re-issued RPC attempt lands in the statement's trace tree
    as an `rpc:retry` leaf with the retried peer labeled."""
    cli = RpcClient("127.0.0.1", 1, timeout=0.2, retries=2)  # dead port
    try:
        with trace.start_trace("query:TestRetry", service="graphd") as tg:
            tid = tg.trace_id
            with pytest.raises(Exception):
                cli.call("storage.get_vertex", space="x", part=0)
        retries = [s for s in _spans_of(tid) if s["name"] == "rpc:retry"]
        assert len(retries) >= 2
        assert all(s["attrs"]["peer"] == "127.0.0.1:1" for s in retries)
        assert all("attempt" in s["attrs"] for s in retries)
    finally:
        cli.close()


def test_breaker_transitions_traced(clean_faults):
    get_config().set_dynamic("breaker_failure_threshold", 2)
    get_config().set_dynamic("breaker_reset_secs", 0.05)
    cli = RpcClient("127.0.0.1", 1, timeout=0.2, retries=0)
    try:
        with trace.start_trace("query:TestBreaker",
                               service="graphd") as tg:
            tid = tg.trace_id
            for _ in range(3):
                with pytest.raises(Exception):
                    cli.call("storage.get_vertex", space="x", part=0)
            time.sleep(0.08)
            # half-open probe admitted, fails, re-opens
            with pytest.raises(Exception):
                cli.call("storage.get_vertex", space="x", part=0)
        br_spans = [s for s in _spans_of(tid)
                    if s["name"] == "rpc:breaker"]
        states = [s["attrs"]["to"] for s in br_spans]
        assert "open" in states and "half_open" in states, states
        assert all(s["attrs"]["peer"] == "127.0.0.1:1" for s in br_spans)
    finally:
        cli.close()
        get_config().dynamic_layer.pop("breaker_failure_threshold", None)
        get_config().dynamic_layer.pop("breaker_reset_secs", None)


def test_dedup_fast_path_traced_and_costed(cluster, clean_faults):
    """A re-sent tokened write answered from the dedup window produces
    a `storage:dedup_hit` remote span in the caller's trace and a
    `dedup_hits` field in the reply cost record."""
    sc = StorageClient(cluster.meta_clients[0])
    pid = sc.part_of("tel", 1)
    params = {"cmds": [to_wire(["upd_vertex", 1, "Person",
                                {"age": 33}])],
              "cat_ver": cluster.meta_clients[0].version,
              "token": ["wtrace", 71]}
    sc._call_part("tel", pid, "storage.write", dict(params))
    cc = CostRecorder()
    with trace.start_trace("query:TestDedup", service="graphd") as tg:
        tid = tg.trace_id
        with use_cost(cc):
            sc._call_part("tel", pid, "storage.write", dict(params))
    hits = [s for s in _spans_of(tid)
            if s["name"] == "storage:dedup_hit"]
    assert hits and hits[0].get("remote"), \
        "dedup fast path did not land in the trace"
    assert hits[0]["attrs"]["writer"] == "wtrace"
    assert cc.as_dict().get("dedup_hits", 0) >= 1
    sc.close()


def test_profile_fused_pipeline_segments():
    """A fused TpuMatchPipeline node is no longer opaque: PROFILE shows
    each segment's own wall time / rows (and device µs where a segment
    dispatched)."""
    from test_tpu import P, random_store  # noqa: E402 — shared harness
    from nebula_tpu.tpu import TpuRuntime, make_mesh

    st = random_store(3, n=60, avg_deg=4)
    eng = QueryEngine(st, tpu_runtime=TpuRuntime(make_mesh(P)))
    s = eng.new_session()
    eng.execute(s, "USE g")
    q = ("MATCH (a:person)-[:knows]->(b:person) WHERE id(a) IN [1,2,3] "
         "WITH DISTINCT b MATCH (b)-[:knows]->(c:person) "
         "RETURN id(b) AS x, id(c) AS y ORDER BY x, y")
    plain = eng.execute(s, q)
    assert plain.error is None
    prof = eng.execute(s, "PROFILE " + q)
    assert prof.error is None
    if "TpuMatchPipeline" in (prof.plan_desc or ""):
        assert "segment:" in prof.plan_desc, prof.plan_desc
        assert "segment:result" in prof.plan_desc
    assert sorted(map(tuple, prof.data.rows)) == \
        sorted(map(tuple, plain.data.rows))


# -- SLO engine -------------------------------------------------------------


def test_show_slo_reports_burn_rates():
    from nebula_tpu.utils.slo import slo_engine
    eng = QueryEngine()
    s = eng.new_session()
    eng.execute(s, "YIELD 1")
    eng.execute(s, "GOGO")            # syntax error → availability bad
    slo_engine().tick()
    r = eng.execute(s, "SHOW SLO")
    assert r.ok, r.error
    assert r.data.column_names == ["Objective", "Window", "Target",
                                   "Total", "Bad", "Bad Ratio",
                                   "Burn Rate"]
    rows = r.data.rows
    assert len(rows) == 6             # 2 objectives × 3 windows
    avail = [x for x in rows if x[0] == "availability"]
    assert len(avail) == 3 and all(x[6] >= 0 for x in avail)
    # the 6h window has seen at least one error by now → nonzero burn
    a6 = next(x for x in avail if x[1] == "6h")
    assert a6[3] > 0 and a6[6] > 0
    # gauges published for federation
    snap = stats().snapshot()
    assert "slo_burn_availability_1h" in snap
    assert "slo_burn_latency_6h" in snap


def test_slo_history_survives_subsecond_polling(monkeypatch):
    """Burst collapse must KEEP older snapshots, not replace them — a
    0.5s poller must still leave real window bases behind."""
    import nebula_tpu.utils.slo as slo_mod
    eng = slo_mod.SloEngine()
    clock = {"t": 1000.0}
    monkeypatch.setattr(slo_mod.time, "monotonic",
                        lambda: clock["t"])
    for i in range(20):               # 10s of 0.5s polls
        clock["t"] = 1000.0 + i * 0.5
        eng.tick()
    assert len(eng._snaps) >= 10, \
        "sub-second polling starved the snapshot history"
    ages = [clock["t"] - ts for ts, _ in eng._snaps]
    assert max(ages) >= 9.0, f"oldest base too fresh: {ages}"


def test_slo_endpoint():
    from nebula_tpu.cluster.webservice import WebService
    ws = WebService(role="graphd")
    ws.start()
    try:
        rows = json.loads(urllib.request.urlopen(
            f"http://{ws.addr}/slo").read())
        assert len(rows) == 6
        assert {r["window"] for r in rows} == {"5m", "1h", "6h"}
    finally:
        ws.stop()


# -- metric federation ------------------------------------------------------


def test_federation_scrapes_and_labels(cluster):
    from nebula_tpu.cluster.federation import MetricFederator
    from nebula_tpu.cluster.webservice import WebService
    ws_g = WebService(role="graphd")
    ws_s = WebService(role="storaged")
    ws_g.start()
    ws_s.start()
    try:
        # daemons report their webservice addr via the heartbeat
        graph_mc = cluster.meta_clients[-1]
        stor_mc = cluster.meta_clients[0]
        graph_mc.ws_addr = ws_g.addr
        stor_mc.ws_addr = ws_s.addr
        graph_mc.heartbeat_once()
        stor_mc.heartbeat_once()
        fed = MetricFederator(cluster.metads[0])
        targets = fed.targets()
        assert {t[2] for t in targets} >= {ws_g.addr, ws_s.addr}
        merged = fed.scrape_once()
        assert f'instance="{graph_mc.my_addr}"' in merged
        assert 'role="graphd"' in merged and 'role="storaged"' in merged
        # every sample line is labeled (federation invariant)
        for ln in merged.splitlines():
            if ln and not ln.startswith("#"):
                assert 'instance="' in ln, ln
        status = fed.scrape_status()
        assert all(s["ok"] for s in status.values())
        # dead target counts an error, does not break the merge
        ws_s.stop()
        fed.scrape_once()
        assert any(not s["ok"] for s in fed.scrape_status().values())
    finally:
        ws_g.stop()
        try:
            ws_s.stop()
        except Exception:  # noqa: BLE001 — already stopped above
            pass


def test_federation_label_injection_grammar():
    from nebula_tpu.cluster.federation import _inject_labels
    text = ('# TYPE a counter\na 3\n'
            'b{op="x",le="+Inf"} 7\nc_sum 1.5\n')
    out = _inject_labels(text, "1.2.3.4:9779", "storaged")
    assert 'a{instance="1.2.3.4:9779",role="storaged"} 3' in out
    assert 'b{op="x",le="+Inf",instance="1.2.3.4:9779",' \
           'role="storaged"} 7' in out


# -- metric catalogue lint --------------------------------------------------


def _emitted_metric_names():
    call_pat = re.compile(
        r'\.(?:inc|inc_labeled|observe|gauge_labeled|gauge|add_value)\(\s*'
        r'["\']([A-Za-z_][A-Za-z0-9_.]*)["\']')
    slo_pat = re.compile(r'["\'](slo_burn_[a-z0-9_]+)["\']')
    names = set()
    for p in (REPO / "nebula_tpu").rglob("*.py"):
        src = p.read_text()
        names.update(call_pat.findall(src))
        names.update(slo_pat.findall(src))
    # dynamically-composed names (prefix + suffix): verified here so
    # the allowlist can't outlive the code that emits them
    pushdown = (REPO / "nebula_tpu/cluster/pushdown.py").read_text()
    assert 'stats_prefix + "_scanned"' in pushdown
    assert 'stats_prefix + "_shipped"' in pushdown
    assert '"storage_pushdown"' in \
        (REPO / "nebula_tpu/cluster/storage_service.py").read_text()
    names.update({"storage_pushdown_scanned",
                  "storage_pushdown_shipped"})
    # the hop programs' engagement counters are one loop over the
    # programs' result keys (tpu/fetch.py `_ENGAGEMENT`)
    from nebula_tpu.tpu.fetch import _ENGAGEMENT
    assert 'm.inc(f"tpu_hop_{k}"' in \
        (REPO / "nebula_tpu/tpu/runtime.py").read_text()
    names.update(f"tpu_hop_{k}" for k in _ENGAGEMENT)
    # the statement phase ledger is one batched update by constant
    # names (utils/trace.py); process_cpu_s exists in snapshots only
    from nebula_tpu.utils import trace
    assert "inc_labeled_many(" in \
        (REPO / "nebula_tpu/utils/trace.py").read_text()
    assert 'out["process_cpu_s"]' in \
        (REPO / "nebula_tpu/utils/stats.py").read_text()
    names.update({trace.PHASE_US, trace.PHASE_N, "process_cpu_s"})
    return names


def _catalogued_metric_names():
    doc = (REPO / "docs/OBSERVABILITY.md").read_text()
    section = doc.split("## Metric catalogue", 1)
    assert len(section) == 2, "OBSERVABILITY.md lost its catalogue"
    return set(re.findall(r"^- `([A-Za-z0-9_.]+)`", section[1],
                          re.MULTILINE))


@pytest.mark.lint
def test_metric_catalogue_lint():
    """Every metric the registries emit is documented, and every
    documented metric is emitted — the catalogue cannot drift."""
    emitted = _emitted_metric_names()
    documented = _catalogued_metric_names()
    undocumented = emitted - documented
    stale = documented - emitted
    assert not undocumented, \
        f"metrics missing from docs/OBSERVABILITY.md catalogue: " \
        f"{sorted(undocumented)}"
    assert not stale, \
        f"catalogued metrics no code emits: {sorted(stale)}"


# -- span catalogue lint (ISSUE 9 satellite) --------------------------------


def _emitted_span_names():
    """Every span / phase / root-trace name the source tree emits,
    with dynamic f-string segments (`{node.kind}`) normalized to `*`
    so `exec:{node.kind}` and the catalogue's `exec:*` compare equal.
    A device launch opens its phases' spans by name through
    `trace.phase(phases, name)`."""
    pat = re.compile(
        r'(?:trace|_trace|_t)\.(?:(?:span|record_phase|mark|start_trace)\('
        r'|phase\(phases,)\s*(f?)["\']([^"\']+)["\']')
    names = set()
    for p in (REPO / "nebula_tpu").rglob("*.py"):
        for isf, name in pat.findall(p.read_text()):
            if isf:
                name = re.sub(r"\{[^}]*\}", "*", name)
            names.add(name)
    return names


def _catalogued_span_names():
    doc = (REPO / "docs/OBSERVABILITY.md").read_text()
    section = doc.split("## Span catalogue", 1)
    assert len(section) == 2, "OBSERVABILITY.md lost its span catalogue"
    body = section[1].split("\n## ", 1)[0]
    return set(re.findall(r"^- `([A-Za-z0-9_.:*]+)`", body,
                          re.MULTILINE))


@pytest.mark.lint
def test_span_catalogue_lint():
    """Every span/phase name the source emits is documented and every
    documented span name is emitted — so a renamed span cannot
    silently orphan dashboards or the Perfetto export."""
    emitted = _emitted_span_names()
    documented = _catalogued_span_names()
    assert emitted, "span scan found nothing — the regex rotted"
    undocumented = emitted - documented
    stale = documented - emitted
    assert not undocumented, \
        f"spans missing from docs/OBSERVABILITY.md span catalogue: " \
        f"{sorted(undocumented)}"
    assert not stale, \
        f"catalogued spans no code emits: {sorted(stale)}"
