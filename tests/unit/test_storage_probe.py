"""The freshness probe and the write census as one `storage.probe`
request a storaged HOST (PR 44): `StorageClient.probe`, its handler
`StorageService.rpc_probe`, and the two callers in `cluster/dstore.py`
(`_SpaceView.epoch`, `_census_probe`).

Same questions, same moments, same answers as the per-part
`storage.part_stats` fan-out they replace, in fewer messages: every
test here either counts the messages or holds the new answers to the
old fan-out's, which survives as the fallback of a host that refused.
"""
import time

import pytest

from nebula_tpu.cluster.launcher import LocalCluster
from nebula_tpu.cluster.storage_client import StorageError
from nebula_tpu.graphstore.store import Partition
from nebula_tpu.tpu.device import make_mesh
from nebula_tpu.tpu.runtime import TpuRuntime
from nebula_tpu.utils import trace
from nebula_tpu.utils.config import get_config
from nebula_tpu.utils.stats import stats

COUNTERS = ("storage_probe_rpcs", "storage_probe_parts",
            "storage_probe_fallback_parts")
DELTA_KEYS = ("tpu_delta_max_edges", "tpu_delta_compact_watermark")


def counters():
    snap = stats().snapshot()
    return {k: snap.get(k, 0) for k in COUNTERS}


def moved(before):
    now = counters()
    return tuple(now[k] - before[k] for k in COUNTERS)


def latest(name):
    for t in trace.trace_store().list():
        if t["name"] == name:
            return trace.trace_store().get(t["tid"])
    raise AssertionError(f"no {name} trace recorded")


def named(entry, name):
    return [s for s in entry["spans"] if s["name"] == name]


def old_fanout(store, space, writer=None):
    """What the per-part walk gives: {pid: (epoch, total, from)}."""
    params = {} if writer is None else {"writer": writer}
    return {pid: (r["epoch"], r.get("writes_total", 0), r.get("writes_from", 0))
            for pid, r in store.sc.fanout(
                space, {p: dict(params) for p in store.sc.all_parts(space)},
                "storage.part_stats")}


def settled(fn, timeout=10.0):
    """`fn()` once two readings 0.1 s apart agree (followers apply
    behind their leader, and each host counts its own applies)."""
    deadline = time.monotonic() + timeout
    last = fn()
    while time.monotonic() < deadline:
        time.sleep(0.1)
        now = fn()
        if now == last:
            return now
        last = now
    raise AssertionError(f"never settled: {last}")


def run(cl, q, timeout=30.0):
    """`q` through `cl`, again while a part's election is still in
    flight (three replicas a part under a loaded test machine)."""
    deadline = time.monotonic() + timeout
    r = cl.execute(q)
    while r.error is not None and "unreachable" in r.error \
            and time.monotonic() < deadline:
        time.sleep(0.2)
        r = cl.execute(q)
    assert r.error is None, (q, r.error)
    return r


def wait_part_leaders(c, space, timeout=30.0):
    """Every part of `space` led by ONE live replica, the same at two
    readings 0.1 s apart (tests/chaos/harness.py `wait_part_leaders`,
    and held): a write sent into an election spends `run`'s retries on
    `part_leader_changed`, under a loaded machine all thirty seconds of
    them."""
    sid = c.storageds[0].meta.catalog.get_space(space).space_id
    n_parts = len(c.meta_clients[0].parts_of(space))

    def leaders():
        led = []
        for pid in range(n_parts):
            who = [ss.my_addr for ss in c.storageds
                   if (sid, pid) in ss.parts and ss.parts[(sid, pid)].is_leader()]
            led.append(who[0] if len(who) == 1 else None)
        return led
    deadline = time.monotonic() + timeout
    last = None
    while time.monotonic() < deadline:
        now = leaders()
        if None not in now and now == last:
            return
        last = now
        time.sleep(0.1)
    raise AssertionError(f"{space}: no settled leader for every part: {last}")


def served_cluster(tmp_path, n_storage, replica_factor, space):
    """A cluster whose graphd holds a device runtime with the delta
    plane armed; eight parts, 1 knows 2."""
    get_config().set_dynamic_many({"tpu_delta_max_edges": 64,
                                   "tpu_delta_compact_watermark": 2.0})
    rt = TpuRuntime(make_mesh())
    c = LocalCluster(n_meta=1, n_storage=n_storage, n_graph=1,
                     data_dir=str(tmp_path), tpu_runtime=rt)
    try:
        cl = c.client()
        r = cl.execute(f"CREATE SPACE {space}(partition_num=8, "
                       f"replica_factor={replica_factor}, vid_type=INT64)")
        assert r.error is None, r.error
        c.reconcile_storage()
        wait_part_leaders(c, space)
        for q in [f"USE {space}", "CREATE TAG T()", "CREATE EDGE E(w int)",
                  "INSERT VERTEX T() VALUES " + ", ".join(f"{v}:()" for v in range(1, 9)),
                  "INSERT EDGE E(w) VALUES 1->2:(1)"]:
            run(cl, q)
    except BaseException:
        c.stop()
        unset_delta_flags()
        raise
    return c, cl, rt


def unset_delta_flags():
    cfg = get_config()
    with cfg.lock:
        for k in DELTA_KEYS:
            cfg.dynamic_layer.pop(k, None)


def friends(cl, v=1):
    r = run(cl, f"GO FROM {v} OVER E YIELD dst(edge) AS d")
    return sorted(x[0] for x in r.data.rows)


@pytest.fixture(scope="module")
def one_host(tmp_path_factory):
    """The served cells' layout: ONE storaged, eight parts."""
    c, cl, rt = served_cluster(tmp_path_factory.mktemp("probe1"), 1, 1, "p1")
    try:
        assert friends(cl) == [2]           # pins, arms the plane
        yield c, cl, rt
    finally:
        c.stop()
        unset_delta_flags()


@pytest.mark.parametrize("write, probes, censuses", [
    (False, 1, 0),      # a read: the probe
    (True, 3, 2),       # a read after a write: the probe, the census, the census under the gate
    (False, 1, 0),      # and again: the epoch is asked anew, none survives a statement
])
def test_one_storaged_is_asked_once_a_question(one_host, write, probes, censuses):
    """On one storaged with eight parts every probe and every census is
    ONE storage RPC that answers all eight parts (1 : 8 : 0), under the
    spans the statement had: `tpu:snapshot_check` before every dispatch,
    two `tpu:delta_census` an apply."""
    c, cl, rt = one_host
    want = friends(cl)
    if write:
        dst = max(want) + 1
        assert cl.execute(f"INSERT EDGE E(w) VALUES 1->{dst}:({dst})").error is None
        want.append(dst)
    dev = rt.snapshots["p1"]
    c0 = counters()
    assert friends(cl) == want
    assert rt.snapshots["p1"] is dev, "the read re-pinned"
    assert moved(c0) == (probes, 8 * probes, 0)
    go = latest("query:Go")
    check = named(go, "tpu:snapshot_check")
    census = named(go, "tpu:delta_census")
    assert len(check) == 1 and len(census) == censuses
    asked = named(go, "storage:storage.probe")
    assert len(asked) == probes and all(s["attrs"]["parts"] == 8 for s in asked)
    assert sorted(s["psid"] for s in asked) == \
        sorted(s["sid"] for s in check + census)
    # the probe and the censuses are the statement's only messages to a
    # storaged but the re-read of the written key
    rpcs = [s["name"] for s in go["spans"]
            if s["name"].startswith("rpc:storage.") and not s.get("remote")]
    assert rpcs.count("rpc:storage.probe") == probes
    assert "rpc:storage.part_stats" not in rpcs
    assert set(rpcs) <= {"rpc:storage.probe", "rpc:storage.get_edge",
                         "rpc:storage.get_vertex"}
    assert (len(rpcs) > probes) == write


def test_the_handler_counts_no_vertex_and_no_edge(one_host, monkeypatch):
    """`rpc_probe` answers from `sd.epoch` and the census alone: with
    `Partition.edge_count` raising, the probe and the census still
    answer while `stats()`, which wants the counts, fails."""
    c, _cl, _rt = one_host
    store = c.graphds[0].store
    want = old_fanout(store, "p1", store.writer_id)

    def boom(self):
        raise AssertionError("edge_count called")
    monkeypatch.setattr(Partition, "edge_count", boom)
    with pytest.raises(StorageError, match="edge_count called"):
        store.stats("p1")
    assert store.sc.probe("p1", writer=store.writer_id) == want
    assert store.space("p1").epoch == max(e for e, _t, _m in want.values())


@pytest.fixture(scope="module")
def three_hosts(tmp_path_factory):
    """Three storageds, every part on all three (`replica_factor` 3)."""
    c, cl, rt = served_cluster(tmp_path_factory.mktemp("probe3"), 3, 3, "p3")
    try:
        assert friends(cl) == [2]
        yield c, cl, rt
    finally:
        c.stop()
        unset_delta_flags()


@pytest.mark.parametrize("census", [False, True])
def test_three_hosts_each_leader_host_is_asked_once(three_hosts, census):
    """One request a distinct first-tried (leader) host; the epoch is
    exactly the maximum the old fan-out reads and the census exactly its
    per-part tuples."""
    c, _cl, _rt = three_hosts
    store = c.graphds[0].store
    writer = store.writer_id if census else None
    hosts = {reps[0] for reps in store.meta.parts_of("p3")}
    assert 1 < len(hosts) <= 3             # several: asked concurrently
    want = settled(lambda: old_fanout(store, "p3", writer))
    c0 = counters()
    t = trace.start_trace("probe3")
    with t:
        got = store.sc.probe("p3", writer=writer)
    assert got == want and len(got) == 8
    assert moved(c0) == (len(hosts), 8, 0)
    spans = trace.trace_store().get(t.trace_id)["spans"]
    asked = [s for s in spans if s["name"] == "storage:storage.probe"]
    assert {s["attrs"]["peer"] for s in asked} == hosts and len(asked) == len(hosts)
    assert len([s for s in spans if s["name"] == "rpc:storage.probe"]) == len(hosts)
    if census:
        assert any(t > 0 and m > 0 for _e, t, m in got.values())
        assert store._census_probe("p3") == want
    else:
        assert all((t, m) == (0, 0) for _e, t, m in got.values())
        assert store.space("p3").epoch == store.stats("p3")["epoch"] == \
            max(e for e, _t, _m in want.values())


def test_a_foreign_writer_breaks_the_log_through_the_probe(tmp_path):
    """The census that rides `storage.probe` still proves coverage: our
    own writes keep the log whole, another graphd's write breaks it
    (`delta_records` -> None) and the runtime would rebuild."""
    c = LocalCluster(n_meta=1, n_storage=2, n_graph=2, data_dir=str(tmp_path))
    try:
        mine, theirs = c.client(graphd=0), c.client(graphd=1)
        assert mine.execute("CREATE SPACE fw(partition_num=4, replica_factor=1, "
                            "vid_type=INT64)").error is None
        c.reconcile_storage()
        for q in ["USE fw", "CREATE TAG T()", "CREATE EDGE E(w int)",
                  "INSERT VERTEX T() VALUES 1:(), 2:(), 3:()"]:
            assert mine.execute(q).error is None, q
        store = c.graphds[0].store
        store.delta_watch("fw")
        assert mine.execute("INSERT EDGE E(w) VALUES 1->2:(1)").error is None
        rec = store.delta_records("fw")
        assert rec is not None and rec[0], "our own write must keep the log whole"
        assert rec[1] == store.stats("fw")["epoch"]
        deadline = time.monotonic() + 10
        r = theirs.execute("USE fw")
        while r.error is not None and time.monotonic() < deadline:
            time.sleep(0.1)                 # the second graphd's catalog catches up
            r = theirs.execute("USE fw")
        assert r.error is None, r.error
        assert theirs.execute("INSERT EDGE E(w) VALUES 2->3:(2)").error is None
        c0 = counters()
        assert store.delta_records("fw") is None
        assert moved(c0)[0] >= 1 and moved(c0)[2] == 0
    finally:
        c.stop()


def test_a_stopped_hosts_parts_fall_back_to_the_per_part_walk(tmp_path):
    """One of three hosts stopped: the grouped request to it fails, ITS
    parts go down the per-part walk (which finds another replica), the
    epoch is still the old fan-out's, and a read after an acknowledged
    write still sees the row."""
    c, cl, rt = served_cluster(tmp_path, 3, 3, "fo")
    try:
        assert friends(cl) == [2]
        store = c.graphds[0].store
        settled(lambda: old_fanout(store, "fo"))

        def epochs():
            # the probe's and the old fan-out's, once the surviving
            # replicas have applied what their leaders have
            return settled(lambda: (store.space("fo").epoch,
                                    store.stats("fo")["epoch"]))
        pm = store.meta.parts_of("fo")
        dead = pm[0][0]
        lost = [pid for pid, reps in enumerate(pm) if reps[0] == dead]
        c.stop_storaged([s.addr for s in c.storage_servers].index(dead))
        c0 = counters()
        got = store.sc.probe("fo", writer=store.writer_id)
        rpcs, parts, fell = moved(c0)
        assert fell == len(lost) > 0 and parts == 8 - len(lost)
        assert len(got) == 8
        assert len(set(epochs())) == 1
        # the write waits for the lost parts' elections (`run`'s retries
        # are the backstop, not the wait); acknowledged, the next read
        # sees it
        wait_part_leaders(c, "fo")
        run(cl, "INSERT EDGE E(w) VALUES 1->3:(3)")
        assert friends(cl) == [2, 3]
        assert len(set(epochs())) == 1
    finally:
        c.stop()
        unset_delta_flags()
