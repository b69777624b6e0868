"""Multi-lane batched device execution (ISSUE 15): batch forming,
per-lane de-mux parity, the one-dispatch-slot contract with the PR 8
shed plane, KILL/deadline lane detach (mid-form and mid-flight), the
SHOW QUERIES Batch column, and UPDATE CONFIGS-updatable flags."""
import random
import threading
import time

import pytest

from nebula_tpu.exec.engine import QueryEngine
from nebula_tpu.graphstore.schema import PropDef, PropType
from nebula_tpu.graphstore.store import GraphStore
from nebula_tpu.utils.config import get_config
from nebula_tpu.utils.failpoints import fail
from nebula_tpu.utils.stats import WorkCounters, stats, use_work
from nebula_tpu.utils.workload import dispatch_table, live_registry

tpu = pytest.importorskip("nebula_tpu.tpu")
from nebula_tpu.tpu import TpuRuntime, make_mesh          # noqa: E402
from nebula_tpu.tpu.batch import batch_former             # noqa: E402

GO_TMPL = "GO 2 STEPS FROM {seed} OVER E YIELD dst(edge) AS d"


def batched_store(n=60, deg=4):
    rng = random.Random(11)
    st = GraphStore()
    st.create_space("bt", partition_num=4, vid_type="INT64")
    st.catalog.create_tag("bt", "P", [PropDef("x", PropType.INT64)])
    st.catalog.create_edge("bt", "E", [PropDef("w", PropType.INT64)])
    for v in range(n):
        st.insert_vertex("bt", v, "P", {"x": v})
    for v in range(n):
        for _ in range(deg):
            st.insert_edge("bt", v, "E", rng.randrange(n), 0, {"w": v})
    return st


@pytest.fixture(scope="module")
def rt():
    # single-chip mesh: the lane axis is a local_mode program
    return TpuRuntime(make_mesh(1))


@pytest.fixture()
def clean():
    fail.reset()
    batch_former().reset()
    yield
    fail.reset()
    batch_former().reset()
    cfg = get_config()
    with cfg.lock:
        for k in ("batch_max_lanes", "batch_wait_us",
                  "query_timeout_secs", "flight_sample_rate"):
            cfg.dynamic_layer.pop(k, None)


def device_engine(rt, **kw):
    eng = QueryEngine(batched_store(**kw), tpu_runtime=rt)
    s = eng.new_session()
    assert eng.execute(s, "USE bt").error is None
    return eng


@pytest.fixture()
def company():
    """Two dummy live registrations so the batch former's concurrency
    hint is deterministically TRUE regardless of thread arrival order
    (in production the hint comes from real concurrent statements or
    the admission drain burst)."""
    a = live_registry().register(qid=-101, session=0, user="t",
                                 stmt="dummy", kind="Go")
    b = live_registry().register(qid=-102, session=0, user="t",
                                 stmt="dummy", kind="Go")
    yield
    if a is not None:
        live_registry().deregister(-101)
    if b is not None:
        live_registry().deregister(-102)


def _run_stmt(eng, stmt, out, key, errs):
    try:
        s = eng.new_session()
        eng.execute(s, "USE bt")
        wc = WorkCounters()
        with use_work(wc):
            rs = eng.execute(s, stmt)
        out[key] = (rs, wc.as_dict())
    except Exception as ex:  # noqa: BLE001
        errs.append(repr(ex))


def _concurrent(eng, stmts):
    out, errs = {}, []
    ths = [threading.Thread(target=_run_stmt,
                            args=(eng, stmt, out, key, errs),
                            daemon=True)
           for key, stmt in stmts.items()]
    for t in ths:
        t.start()
    for t in ths:
        t.join(60)
    assert not errs, errs[:3]
    return out


def _wait_for(pred, timeout=10.0, msg="condition"):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        got = pred()
        if got:
            return got
        time.sleep(0.01)
    pytest.fail(f"timed out waiting for {msg}")


# -- forming + de-mux parity ------------------------------------------------


def test_batched_launch_shares_and_demuxes(rt, clean, company):
    """K compatible concurrent GO statements form ONE multi-lane
    launch; each statement's rows and deterministic WorkCounters equal
    its own solo run (per-lane de-mux through the per-statement
    attribution machinery)."""
    eng = device_engine(rt)
    seeds = [1, 2, 3, 5]
    truth = {}
    for sd in seeds:
        out = {}
        _run_stmt(eng, GO_TMPL.format(seed=sd), out, sd, [])
        rs, wc = out[sd]
        assert rs.error is None, rs.error
        truth[sd] = (sorted(map(repr, rs.data.rows)), wc)
    get_config().set_dynamic_many({"batch_max_lanes": 8,
                                   "batch_wait_us": 300_000})
    s0 = stats().snapshot()
    out = _concurrent(eng, {sd: GO_TMPL.format(seed=sd)
                            for sd in seeds})
    s1 = stats().snapshot()
    for sd in seeds:
        rs, wc = out[sd]
        assert rs.error is None, rs.error
        assert sorted(map(repr, rs.data.rows)) == truth[sd][0], \
            f"seed {sd}: batched rows differ from solo truth"
        assert wc == truth[sd][1], \
            f"seed {sd}: batched work counters differ from solo truth"
    formed = s1.get("tpu_batches_formed", 0) \
        - s0.get("tpu_batches_formed", 0)
    runs = s1.get("tpu_kernel_runs", 0) - s0.get("tpu_kernel_runs", 0)
    assert formed >= 1, "no batch formed under concurrent load"
    # sharing is real: fewer launches than statements (ledger proof)
    assert runs < len(seeds), (runs, len(seeds))


def test_solo_statement_skips_the_window(rt, clean):
    """Batching ON with no concurrent company: the statement takes the
    solo dispatch path — no group, no forming wait, no batch metrics
    (single-query latency unchanged)."""
    eng = device_engine(rt)
    get_config().set_dynamic_many({"batch_max_lanes": 8,
                                   "batch_wait_us": 500_000})
    s0 = stats().snapshot()
    out = {}
    _run_stmt(eng, GO_TMPL.format(seed=7), out, 7, [])
    rs, _ = out[7]
    assert rs.error is None, rs.error
    s1 = stats().snapshot()
    assert s1.get("tpu_batches_formed", 0) == \
        s0.get("tpu_batches_formed", 0)
    assert not batch_former().forming()


def test_batch_form_failpoint_raise_dispatches_solo(rt, clean, company):
    """`tpu:batch_form` armed with raise: enrollment is rejected and
    the statement dispatches SOLO (rows still correct — never host
    fallback, never an error)."""
    eng = device_engine(rt)
    out = {}
    _run_stmt(eng, GO_TMPL.format(seed=9), out, "truth", [])
    truth = sorted(map(repr, out["truth"][0].data.rows))
    get_config().set_dynamic_many({"batch_max_lanes": 8,
                                   "batch_wait_us": 300_000})
    fail.arm("tpu:batch_form", "raise")
    s0 = stats().snapshot()
    out = {}
    _run_stmt(eng, GO_TMPL.format(seed=9), out, 9, [])
    rs, _ = out[9]
    assert rs.error is None, rs.error
    assert sorted(map(repr, rs.data.rows)) == truth
    s1 = stats().snapshot()
    assert s1.get("tpu_batches_formed", 0) == \
        s0.get("tpu_batches_formed", 0)


# -- the one driver: a shared launch fetches as a solo one does -------------


def _go_traces(seen):
    """The `query:Go` traces recorded since `seen` (a set of trace ids,
    updated in place), newest first."""
    from nebula_tpu.utils import trace
    new = [trace.trace_store().get(t["tid"])
           for t in trace.trace_store().list(limit=256)
           if t["name"] == "query:Go" and t["tid"] not in seen]
    seen.update(e["tid"] for e in new)
    return new


def test_warm_lane_launch_fetches_once_and_counts_an_undershoot(
        clean, company, monkeypatch):
    """A lane-batched launch takes the speculative single-phase fetch
    with it: the second launch of a warm shape brings meta and capture
    back in ONE `device:fetch` phase per lane and counts no
    `tpu_refetches`; a speculation that undershoots falls back to the
    exact refetch and counts one; rows equal the solo run's every
    time."""
    from nebula_tpu.tpu import fetch
    # no floor under the single slice: a speculation of 1 slot undershoots
    monkeypatch.setattr(fetch, "SLICE_MIN", 1)
    rt = TpuRuntime(make_mesh(1))       # no kept size known yet
    eng = device_engine(rt)
    seeds = [1, 2, 3, 5]
    stmts = {sd: GO_TMPL.format(seed=sd) for sd in seeds}
    truth = {}
    for sd in seeds:
        out = {}
        _run_stmt(eng, stmts[sd], out, sd, [])
        truth[sd] = sorted(map(repr, out[sd][0].data.rows))
    get_config().set_dynamic_many({"batch_max_lanes": 8,
                                   "batch_wait_us": 300_000})
    seen = set()
    _go_traces(seen)

    def launch():
        s0 = stats().snapshot()
        out = _concurrent(eng, stmts)
        s1 = stats().snapshot()
        assert s1.get("tpu_batches_formed", 0) \
            - s0.get("tpu_batches_formed", 0) == 1
        for sd in seeds:
            assert sorted(map(repr, out[sd][0].data.rows)) == truth[sd]
        fetches = [[s.get("attrs", {}) for s in e["spans"]
                    if s["name"] == "device:fetch"] for e in _go_traces(seen)]
        assert len(fetches) == len(seeds)
        return fetches, (s1.get("tpu_refetches", 0)
                         - s0.get("tpu_refetches", 0))

    def lane_keys():
        return [k for k in rt._fetcher.kmax if "lanes" in k]
    assert not lane_keys()
    cold, refetched = launch()
    # a first run knows no kept size: meta, then the capture's own fetch
    assert all(f == [{}, {"refetch": False}] for f in cold), cold
    assert refetched == 0 and len(lane_keys()) == 1
    warm, refetched = launch()
    assert all(f == [{}] for f in warm), warm
    assert refetched == 0
    rt._fetcher.kmax[lane_keys()[0]] = 1        # a speculation no lane fits in
    under, refetched = launch()
    assert all(f == [{}, {"refetch": True}] for f in under), under
    assert refetched == 1
    assert rt._fetcher.kmax[lane_keys()[0]].max() > 1


def test_solo_statement_keeps_live_device_spans(clean, monkeypatch):
    """A solo statement's `device:put`, `device:dispatch` and
    `device:fetch` are LIVE spans, open while the work they name runs
    (the benchmark's trace reduction labels an idle gap by the spans
    open at its midpoint), in that order with `device:queue` closed
    before them: not phases replayed after the launch, as a shared
    launch's members get them."""
    import jax

    from nebula_tpu.tpu import runtime
    from nebula_tpu.utils import trace
    open_around = {}

    def probed(what, fn):
        def run(*a):
            ctx = trace.current_ctx()
            open_around[what] = ctx.sid if ctx is not None else None
            return fn(*a)
        return run
    real_build, real_seed = runtime.build_traverse_fn, TpuRuntime._seed_builder
    monkeypatch.setattr(runtime, "build_traverse_fn",
                        lambda *a, **kw: probed("device:dispatch",
                                                real_build(*a, **kw)))

    def seed_builder(self, *a, **kw):
        key, fn = real_seed(self, *a, **kw)
        return key, probed("device:put", fn)
    monkeypatch.setattr(TpuRuntime, "_seed_builder", seed_builder)
    eng = device_engine(TpuRuntime(make_mesh(1)))
    out = {}
    _run_stmt(eng, GO_TMPL.format(seed=1), out, "cold", [])  # warms the put
    monkeypatch.setattr(jax, "device_get",
                        probed("device:fetch", jax.device_get))
    seen = set()
    _go_traces(seen)
    open_around.clear()
    _run_stmt(eng, GO_TMPL.format(seed=1), out, "warm", [])
    assert out["warm"][0].error is None, out["warm"][0].error
    (entry,) = _go_traces(seen)
    by_sid = {s["sid"]: s for s in entry["spans"]}
    for name, sid in open_around.items():
        assert sid in by_sid and by_sid[sid]["name"] == name, \
            (name, by_sid.get(sid))
    assert set(open_around) == {"device:put", "device:dispatch",
                                "device:fetch"}
    order = ["device:queue", "device:put", "device:dispatch", "device:fetch"]
    dev = sorted((s for s in entry["spans"] if s["name"] in order),
                 key=lambda s: s["t0"])
    assert [s["name"] for s in dev] == order
    assert round(dev[0]["t0"] * 1e6) + dev[0]["dur_us"] \
        <= round(dev[1]["t0"] * 1e6) + 1


def test_fetch_times_itself(clean, monkeypatch):
    """`tpu_fetch_s` and a statement's `fetch_s` are the seconds `Fetcher.fetch`
    measured itself, two-phase or speculative: the release of the
    rung's device buffers, which waits its turn under concurrent
    sessions (eight of them read 2.4 times the fetch on the chip when it
    was timed around the routine), comes after the routine's timer."""
    seen = []
    from nebula_tpu.tpu.fetch import Fetcher
    real = Fetcher.fetch

    def fetch(self, res, key, fetch_keys, info):
        got = real(self, res, key, fetch_keys, info)
        # the caller still holds the device result it handed in
        assert "cap" in res and got is not res
        seen.append(info["fetch_s"])
        return got
    monkeypatch.setattr(Fetcher, "fetch", fetch)
    st = batched_store()
    rt = TpuRuntime(make_mesh(1))
    s0 = stats().snapshot()
    took = []
    for _ in range(2):                  # two-phase, then speculative
        rows, ts = rt.traverse(st, "bt", [1, 2], ["E"], "out", 2)
        assert rows and ts.retries == 0
        took.append(ts.fetch_s)
    assert took == seen and all(t > 0 for t in took)
    s1 = stats().snapshot()
    assert s1["tpu_fetch_s.sum"] - s0.get("tpu_fetch_s.sum", 0) \
        == pytest.approx(sum(seen))


def test_bucket_jit_and_kept_size_keys_keep_their_form(clean):
    """`.tpu_buckets.json` is read across processes and versions: a
    bucket saved under the solo key form, `(key_fn(()), pow2(seeds)) ->
    (0, ebs)`, starts the ladder AT those budgets (no retry), and the
    program and its kept size are cached under `key_fn(ebs)`."""
    st = batched_store()
    rt = TpuRuntime(make_mesh(1))
    dev = rt.pin(st, "bt")
    epoch = dev.epoch
    # the last element is the delta plane's static shape: armed at
    # default flags, at the capacity the pin worked out
    sig = rt._delta_sig(dev)
    assert sig == ("delta", dev.delta.host.dcap, dev.delta.host.tcap)

    def key(ebs):
        return ("bt", epoch, (("E", "out"),), 2, ebs, None, True, (), (),
                0, sig)
    ebs = (4096, 8192)
    assert rt.init_eb < ebs[0]
    rt._buckets[(key(()), 2)] = (0, ebs)
    rows, ts = rt.traverse(st, "bt", [1, 2], ["E"], "out", 2)
    assert rows and ts.retries == 0 and ts.e_cap == list(ebs)
    assert list(rt._fns) == [key(ebs)] and list(rt._fetcher.kmax) == [key(ebs)]
    assert rt._buckets[(key(()), 2)] == (0, ebs)


# -- PR 8 shed interaction: one dispatch-queue slot per batch ---------------


def test_batch_consumes_one_dispatch_slot(rt, clean, company):
    """ISSUE 15 satellite, amended by the ISSUE 19 re-arm fix: with
    the dispatch gate write-held, K batched statements occupy ZERO
    dispatch slots — the non-full forming group keeps re-arming its
    window instead of queueing behind the hold (batching off shows
    depth K), so turning batching on can never increase the
    `tpu_dispatch_queue_cap` shed rate."""
    eng = device_engine(rt)
    seeds = [1, 2, 3]
    # warm: pin + compile outside the gate-held window
    out = {}
    for sd in seeds:
        _run_stmt(eng, GO_TMPL.format(seed=sd), out, sd, [])
        assert out[sd][0].error is None

    def run_held(batching: bool):
        if batching:
            get_config().set_dynamic_many({"batch_max_lanes": 8,
                                           "batch_wait_us": 150_000})
        else:
            get_config().set_dynamic("batch_max_lanes", 0)
        rt._gate.acquire_write()
        depth = None
        try:
            res, errs = {}, []
            ths = [threading.Thread(
                target=_run_stmt,
                args=(eng, GO_TMPL.format(seed=sd), res, sd, errs),
                daemon=True) for sd in seeds]
            r0 = stats().snapshot().get("tpu_batch_gate_rearms", 0)
            for t in ths:
                t.start()
            if batching:
                # the group's window must EXPIRE under the hold at
                # least twice (proof all three enrolled and are
                # re-arming rather than sitting in the dispatch queue)
                _wait_for(lambda: stats().snapshot().get(
                    "tpu_batch_gate_rearms", 0) >= r0 + 2,
                    msg="forming window re-arms behind held gate")
            else:
                _wait_for(lambda: dispatch_table().queued_depth()
                          >= len(seeds),
                          msg=f"queued depth {len(seeds)}")
            # settle: ALL statements are past forming/enqueue before
            # the depth is judged (the batched case must stay at 0)
            time.sleep(0.4)
            depth = dispatch_table().queued_depth()
        finally:
            rt._gate.release_write()
        for t in ths:
            t.join(30)
        assert not errs, errs
        for sd in seeds:
            assert res[sd][0].error is None, res[sd][0].error
        return depth

    assert run_held(batching=False) == len(seeds)
    assert run_held(batching=True) == 0


# -- cancellation detaches one lane -----------------------------------------


def test_kill_mid_form_detaches_lane(rt, clean, company):
    """KILL QUERY of a statement waiting in a forming group evicts
    only that lane: the victim dies promptly (well before the window
    closes), the batchmate completes with correct rows."""
    eng = device_engine(rt)
    out = {}
    _run_stmt(eng, GO_TMPL.format(seed=2), out, "truth", [])
    truth = sorted(map(repr, out["truth"][0].data.rows))
    get_config().set_dynamic_many({"batch_max_lanes": 8,
                                   "batch_wait_us": 3_000_000})
    res, errs = {}, []
    t_victim = threading.Thread(
        target=_run_stmt,
        args=(eng, GO_TMPL.format(seed=1), res, "victim", errs),
        daemon=True)
    t_mate = threading.Thread(
        target=_run_stmt,
        args=(eng, GO_TMPL.format(seed=2), res, "mate", errs),
        daemon=True)
    t_victim.start()
    t_mate.start()
    row = _wait_for(
        lambda: next((r for r in eng.list_running_queries()
                      if r[3] == GO_TMPL.format(seed=1)), None),
        msg="victim visible")
    _wait_for(lambda: batch_former().forming(), msg="group forming")
    t0 = time.monotonic()
    assert eng.kill_running(sid=row[0], qid=row[1])
    t_victim.join(30)
    killed_after = time.monotonic() - t0
    assert res["victim"][0].error == "ExecutionError: query was killed"
    # the victim left the group long before the 3 s window closed
    assert killed_after < 1.5, killed_after
    t_mate.join(30)
    assert not errs, errs
    assert res["mate"][0].error is None, res["mate"][0].error
    assert sorted(map(repr, res["mate"][0].data.rows)) == truth


def test_kill_mid_flight_discards_only_that_lane(rt, clean, company):
    """KILL QUERY after the batch launched: the victim's lane result
    is discarded at de-mux, the batchmate's rows are exact."""
    eng = device_engine(rt)
    out = {}
    _run_stmt(eng, GO_TMPL.format(seed=3), out, "truth", [])
    truth = sorted(map(repr, out["truth"][0].data.rows))
    get_config().set_dynamic_many({"batch_max_lanes": 2,
                                   "batch_wait_us": 400_000})
    # hold the LAUNCH at the dispatch gate so the kill lands mid-flight
    fail.arm("tpu:dispatch_gate", "delay(0.6)")
    s0 = stats().snapshot()
    res, errs = {}, []
    t_victim = threading.Thread(
        target=_run_stmt,
        args=(eng, GO_TMPL.format(seed=5), res, "victim", errs),
        daemon=True)
    t_mate = threading.Thread(
        target=_run_stmt,
        args=(eng, GO_TMPL.format(seed=3), res, "mate", errs),
        daemon=True)
    t_victim.start()
    t_mate.start()
    row = _wait_for(
        lambda: next((r for r in eng.list_running_queries()
                      if r[3] == GO_TMPL.format(seed=5)), None),
        msg="victim visible")
    # a 2-lane group fills and claims its launch immediately; the gate
    # failpoint then holds the LAUNCHED batch queued in the dispatch
    # table — the kill below provably lands mid-flight
    _wait_for(lambda: dispatch_table().queued_depth() >= 1,
              msg="batched launch queued at the gate")
    assert eng.kill_running(sid=row[0], qid=row[1])
    t_victim.join(30)
    t_mate.join(30)
    fail.reset()
    assert not errs, errs
    assert res["victim"][0].error == "ExecutionError: query was killed"
    assert res["mate"][0].error is None, res["mate"][0].error
    assert sorted(map(repr, res["mate"][0].data.rows)) == truth
    s1 = stats().snapshot()
    assert s1.get("tpu_batches_formed", 0) \
        - s0.get("tpu_batches_formed", 0) == 1


def test_deadline_mid_form_evicts_lane(rt, clean, company):
    """A statement whose deadline budget expires while batch-forming
    fails E_QUERY_TIMEOUT without a launch (the lane withdrew)."""
    eng = device_engine(rt)
    get_config().set_dynamic_many({"batch_max_lanes": 8,
                                   "batch_wait_us": 5_000_000,
                                   "query_timeout_secs": 0.4})
    s0 = stats().snapshot()
    out = {}
    _run_stmt(eng, GO_TMPL.format(seed=4), out, 4, [])
    rs, _ = out[4]
    assert rs.error is not None and "E_QUERY_TIMEOUT" in rs.error, rs
    s1 = stats().snapshot()
    assert s1.get("tpu_batches_formed", 0) == \
        s0.get("tpu_batches_formed", 0)
    # the all-withdrawn group was REMOVED from the forming map — a
    # later compatible statement opens a fresh group instead of
    # joining an expired husk (code-review regression)
    assert not batch_former().forming()


# -- SHOW QUERIES surface ---------------------------------------------------


def test_show_queries_batch_column(rt, clean, company):
    """An enrolled statement shows BatchId/lane in SHOW QUERIES while
    forming/in flight; the column clears after completion."""
    eng = device_engine(rt)
    get_config().set_dynamic_many({"batch_max_lanes": 8,
                                   "batch_wait_us": 1_500_000})
    res, errs = {}, []
    t = threading.Thread(
        target=_run_stmt,
        args=(eng, GO_TMPL.format(seed=6), res, 6, errs), daemon=True)
    t.start()

    def batched_row():
        r = next((r for r in eng.list_running_queries()
                  if r[3] == GO_TMPL.format(seed=6)), None)
        return r if r is not None and r[13] else None

    row = _wait_for(batched_row, msg="Batch column populated")
    bid, lane = row[13].split("/")
    assert int(bid) >= 1 and int(lane) >= 0
    # the statement surface carries the same column
    s2 = eng.new_session()
    rs = eng.execute(s2, "SHOW QUERIES")
    assert rs.ok
    assert rs.data.column_names[-3:] == ["Batch", "Fingerprint",
                                         "GraphAddr"]
    srow = next(r for r in rs.data.rows
                if r[3] == GO_TMPL.format(seed=6))
    assert srow[13] == row[13]
    t.join(30)
    assert not errs, errs
    assert res[6][0].error is None, res[6][0].error
    assert not any(r[13] for r in eng.list_running_queries())


# -- flags ------------------------------------------------------------------


def test_batch_flags_update_configs(rt, clean):
    """batch_max_lanes / batch_wait_us are runtime-updatable via the
    UPDATE CONFIGS multi-key path and read LIVE by the former."""
    eng = device_engine(rt)
    s = eng.new_session()
    rs = eng.execute(s, "UPDATE CONFIGS batch_max_lanes=4, "
                        "batch_wait_us=123")
    assert rs.error is None, rs.error
    assert get_config().get("batch_max_lanes") == 4
    assert get_config().get("batch_wait_us") == 123
    assert batch_former().max_lanes() == 4
    assert batch_former().enabled()
    rs = eng.execute(s, "UPDATE CONFIGS batch_max_lanes=0")
    assert rs.error is None, rs.error
    assert not batch_former().enabled()

# -- mesh composition (ISSUE 17) --------------------------------------------


def test_repin_to_wider_mesh_mid_form_splits_group(clean, company):
    """The compatibility key carries the mesh shape + epoch: statements
    enrolled BEFORE a set_mesh re-shard and statements enrolled AFTER
    it land in DIFFERENT groups (two 2-lane launches, never one merged
    4-lane launch spanning two launch grids), and the pre-repin group —
    whose snapshot the re-pin retired — still yields correct rows via
    the TpuUnavailable host fallback."""
    from nebula_tpu.tpu import make_mesh2

    rt = TpuRuntime(make_mesh(1))        # private runtime: set_mesh below
    eng = device_engine(rt)
    seeds = [1, 2, 3, 5]
    truth = {}
    for sd in seeds:
        out = {}
        _run_stmt(eng, GO_TMPL.format(seed=sd), out, sd, [])
        rs, _ = out[sd]
        assert rs.error is None, rs.error
        truth[sd] = sorted(map(repr, rs.data.rows))

    # max_lanes=3: a pair never fills a group, so the pre-repin pair
    # keeps FORMING for the whole window while set_mesh runs
    get_config().set_dynamic_many({"batch_max_lanes": 3,
                                   "batch_wait_us": 500_000})
    s0 = stats().snapshot()
    out, errs = {}, []
    pre = [threading.Thread(target=_run_stmt,
                            args=(eng, GO_TMPL.format(seed=sd), out, sd,
                                  errs), daemon=True)
           for sd in seeds[:2]]
    for t in pre:
        t.start()
    # wait until both pre-repin statements are enrolled in one group
    _wait_for(lambda: any(len(g.members) == 2
                          for g in batch_former()._groups.values()),
              msg="pre-repin group of 2")
    # re-shard 1 -> 4 parts mid-form: the enrolled group's snapshot is
    # retired (donated buffers) and the mesh epoch bumps
    rt.set_mesh(make_mesh(4))
    post = [threading.Thread(target=_run_stmt,
                             args=(eng, GO_TMPL.format(seed=sd), out, sd,
                                   errs), daemon=True)
            for sd in seeds[2:]]
    for t in post:
        t.start()
    for t in pre + post:
        t.join(60)
    assert not errs, errs[:3]
    s1 = stats().snapshot()
    for sd in seeds:
        rs, _ = out[sd]
        assert rs.error is None, rs.error
        assert sorted(map(repr, rs.data.rows)) == truth[sd], \
            f"seed {sd}: rows wrong across the mid-form re-shard"
    formed = s1.get("tpu_batches_formed", 0) \
        - s0.get("tpu_batches_formed", 0)
    # without the mesh-shape/epoch key the post pair would JOIN the
    # still-forming pre group (3rd member fills it -> one merged
    # 3-lane launch, formed == 1); the epoch key keeps the grids apart
    # as two 2-lane groups
    assert formed == 2, f"expected two 2-lane groups, saw {formed}"


def test_forming_window_rearms_behind_write_gate(rt, clean, company):
    """ISSUE 19 satellite: with the dispatch gate write-held (a repin
    or compaction swap in flight), a partially-formed group whose
    forming window expires RE-ARMS the window instead of sealing and
    queueing a fully-FORMED batch behind the gate with its
    batch_wait_us already spent.  While the hold lasts the group keeps
    re-arming (`tpu_batch_gate_rearms` grows, `tpu_batches_formed`
    stays flat); on release the group launches once, fully formed."""
    eng = device_engine(rt)
    out = {}
    for sd in (1, 2):       # warm: pin + compile outside the hold
        _run_stmt(eng, GO_TMPL.format(seed=sd), out, sd, [])
        assert out[sd][0].error is None
    get_config().set_dynamic_many({"batch_max_lanes": 8,
                                   "batch_wait_us": 20_000})
    r0 = stats().snapshot().get("tpu_batch_gate_rearms", 0)
    f0 = stats().snapshot().get("tpu_batches_formed", 0)
    res, errs = {}, []
    ths = [threading.Thread(target=_run_stmt,
                            args=(eng, GO_TMPL.format(seed=sd),
                                  res, sd, errs),
                            daemon=True) for sd in (1, 2)]
    rt._gate.acquire_write()
    try:
        for t in ths:
            t.start()
        # several expiries come and go under the hold — each one
        # re-arms instead of sealing the 2-lane group
        _wait_for(lambda: stats().snapshot().get(
            "tpu_batch_gate_rearms", 0) >= r0 + 3,
            msg="forming window re-arms behind the write gate")
        assert stats().snapshot().get("tpu_batches_formed", 0) == f0, \
            "group sealed while the dispatch gate was write-held"
    finally:
        rt._gate.release_write()
    for t in ths:
        t.join(30)
    assert not errs, errs[:3]
    for sd in (1, 2):
        assert res[sd][0].error is None, res[sd][0].error
    # the held statements still launched as ONE shared batch
    assert stats().snapshot().get("tpu_batches_formed", 0) == f0 + 1
