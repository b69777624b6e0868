"""Self-healing plane (ISSUE 14), tier-1 half: raft learner semantics
(a learner can NEVER vote or count toward quorum), the resumable
membership task engine (kill between every phase, re-drive converges),
the metad-failover false-dead window, and the dynamic catch-up flag.
The live-load chaos proofs ride in tests/chaos/test_self_heal.py."""
import time

import pytest

from nebula_tpu.cluster.raft import LEADER, LoopbackTransport, RaftPart
from nebula_tpu.utils.config import get_config
from nebula_tpu.utils.failpoints import FailpointError, fail


# ---------------------------------------------------------------------------
# raft learners (LoopbackTransport, in-process groups)
# ---------------------------------------------------------------------------


class Applied:
    def __init__(self):
        import threading
        self.entries = []
        self.lock = threading.Lock()

    def cb(self, idx, data):
        with self.lock:
            self.entries.append((idx, data))

    def data(self):
        with self.lock:
            return [d for _, d in self.entries]


def _mixed_group(tmp_path, n_voters=2, n_learners=1, group="lg",
                 snapshot=False, snapshot_threshold=10_000):
    """n_voters voting members + n_learners learner members."""
    tr = LoopbackTransport()
    voters = [f"v{i}" for i in range(n_voters)]
    learners = [f"l{i}" for i in range(n_learners)]
    parts, apps = [], []
    for nid in voters + learners:
        app = Applied()
        snap_cb = rest_cb = None
        if snapshot:
            def snap_cb(a=app):
                return b"|".join(a.data())

            def rest_cb(b, a=app):
                with a.lock:
                    a.entries = [(0, d) for d in b.split(b"|") if d]
        part = RaftPart(group, nid, voters, tr,
                        str(tmp_path / nid), app.cb,
                        snapshot_cb=snap_cb, restore_cb=rest_cb,
                        election_timeout=(0.05, 0.12),
                        heartbeat_interval=0.02,
                        snapshot_threshold=snapshot_threshold,
                        learners=learners)
        parts.append(part)
        apps.append(app)
    for p in parts:
        p.start()
    return tr, parts, apps


def _wait_leader(parts, timeout=20.0):
    dl = time.monotonic() + timeout
    while time.monotonic() < dl:
        leaders = [p for p in parts if p.is_leader() and p.alive]
        if len(leaders) == 1:
            return leaders[0]
        time.sleep(0.01)
    raise AssertionError("no unique leader elected")


def _applied(app, retried=()):
    """What `app` applied, in order.  An entry in `retried` (`_commit`)
    is read once however often it was applied; every other entry counts
    each time, so a run without a retry is held to exactly-once."""
    seen, out = set(), []
    for d in app.data():
        if not (d in retried and d in seen):
            out.append(d)
        seen.add(d)
    return out


def _wait_data(app, want, retried=(), timeout=20.0):
    dl = time.monotonic() + timeout
    while time.monotonic() < dl:
        if _applied(app, retried) == want:
            return
        time.sleep(0.01)
    raise AssertionError(f"want {want}, got {app.data()}")


def _commit(members, data, retried, timeout=60.0):
    """Commit `data` through whichever member leads NOW, as a real
    client does, and return that leader.  These groups elect within 50
    to 120 ms, so on a busy machine (six xdist workers) a heartbeat
    thread that is starved that long moves the leadership between a
    look at `is_leader()` and the propose, and a follower refuses at
    once: waiting on the clock failed, retrying on the state does not.
    A try waits 20 s, so only a refusal or a deposal makes a second
    one; `data` then joins `retried`, because the first try's entry may
    still commit under the next leader (raft is at-least-once to a
    client that retries) and only such an entry may be applied twice."""
    dl = time.monotonic() + timeout
    tries = 0
    while True:
        ld = next((p for p in members if p.alive and p.is_leader()), None)
        if ld is not None:
            tries += 1
            if tries > 1:
                retried.add(data)
            if ld.propose(data, timeout=20):
                return ld
        assert time.monotonic() < dl, f"{data!r} never committed"
        time.sleep(0.02)


def test_learner_replicates_but_never_counts_toward_quorum(tmp_path):
    """2 voters + 1 learner: entries reach the learner, but with one
    voter dead the group must NOT commit — the learner's ack can never
    substitute for a voter (quorum stays 2-of-2 voters)."""
    tr, parts, apps = _mixed_group(tmp_path, n_voters=2, n_learners=1)
    v0, v1, lrn = parts
    retried = set()
    try:
        leader = _commit([v0, v1], b"a", retried)
        # the learner received and applied the entry (replication works)
        _wait_data(apps[2], [b"a"], retried)
        # kill the OTHER voter: voter quorum is gone; the live learner
        # must not let the leader commit
        other = v1 if leader is v0 else v0
        other.alive = False
        assert leader.propose(b"b", timeout=0.6) is None
        assert not any(b"b" in a.data() for a in apps)
    finally:
        for p in parts:
            p.stop()


def test_learner_never_votes_or_campaigns(tmp_path):
    tr, parts, apps = _mixed_group(tmp_path, n_voters=2, n_learners=1)
    v0, v1, lrn = parts
    try:
        leader = _wait_leader([v0, v1])
        # a learner refuses any vote request, even a well-formed one
        # from a candidate whose log it trails
        r = lrn.handle("request_vote", {
            "_from": leader.node_id, "term": leader.current_term + 1,
            "candidate": leader.node_id,
            "last_log_index": 1 << 30, "last_log_term": 1 << 30})
        assert r["granted"] is False
        # and it never campaigns: both voters die, the learner's
        # election deadline keeps lapsing, it stays a follower forever
        v0.alive = False
        v1.alive = False
        time.sleep(0.5)                 # >> election timeout
        assert lrn.state != LEADER
        assert lrn.current_term <= leader.current_term + 1
    finally:
        for p in parts:
            p.stop()


def test_learner_promote_then_counts_and_votes(tmp_path):
    """After promotion the ex-learner is a full voter: with one
    original voter dead, leader + promoted member form a 2-of-3
    quorum and commits flow again."""
    tr, parts, apps = _mixed_group(tmp_path, n_voters=2, n_learners=1)
    v0, v1, lrn = parts
    retried = set()
    try:
        leader = _commit([v0, v1], b"a", retried)
        _wait_data(apps[2], [b"a"], retried)    # caught up
        fail.reset()
        for p in parts:
            p.update_peers(["v0", "v1", "l0"], [])
        other = v1 if leader is v0 else v0
        other.alive = False
        # against the current leader, whoever it is: the config change
        # may race a heartbeat round
        _commit([v0, v1, lrn], b"b", retried)
        _wait_data(apps[2], [b"a", b"b"], retried)
    finally:
        for p in parts:
            p.stop()


def test_learner_snapshot_install_catchup(tmp_path):
    """A learner added AFTER log compaction catches up via snapshot
    install (the repair path for a part with a compacted WAL)."""
    tr, parts, apps = _mixed_group(tmp_path, n_voters=2, n_learners=0,
                                   snapshot=True, snapshot_threshold=10)
    v0, v1 = parts
    retried = set()
    try:
        want = []
        for i in range(25):             # > snapshot_threshold
            d = f"e{i}".encode()
            leader = _commit(parts, d, retried)
            want.append(d)
        dl = time.monotonic() + 10
        while leader.snap_index == 0 and time.monotonic() < dl:
            time.sleep(0.02)
        assert leader.snap_index > 0, "log never compacted"
        # join the learner now — its WAL is empty, the leader's log
        # starts past the snapshot, so catch-up MUST go through
        # install_snapshot
        app = Applied()

        def rest_cb(b, a=app):
            with a.lock:
                a.entries = [(0, d) for d in b.split(b"|") if d]
        lrn = RaftPart("lg", "l0", ["v0", "v1"], tr,
                       str(tmp_path / "l0"), app.cb,
                       snapshot_cb=lambda: b"", restore_cb=rest_cb,
                       election_timeout=(0.05, 0.12),
                       heartbeat_interval=0.02, learners=["l0"])
        lrn.start()
        for p in parts:
            p.update_peers(["v0", "v1"], ["l0"])
        dl = time.monotonic() + 15
        while time.monotonic() < dl:
            got = _applied(app, retried)
            if got and got == want[-len(got):] and \
                    lrn.applied_index() >= leader.applied_index():
                break
            time.sleep(0.02)
        assert lrn.snap_index > 0, "learner never snapshot-installed"
        parts.append(lrn)
    finally:
        for p in parts:
            p.stop()


# ---------------------------------------------------------------------------
# resumable membership changes (satellite: kill between every phase)
# ---------------------------------------------------------------------------


def _setup_moving_space(client, cluster, parts=4):
    rs = client.execute(
        f"CREATE SPACE mv(partition_num={parts}, replica_factor=1, "
        f"vid_type=INT64)")
    assert rs.error is None, rs.error
    cluster.reconcile_storage()
    for q in ["USE mv", "CREATE TAG item(x int)"]:
        rs = client.execute(q)
        assert rs.error is None, (q, rs.error)
    vals = ", ".join(f"{i}:({i * 10})" for i in range(40))
    rs = client.execute(f"INSERT VERTEX item(x) VALUES {vals}")
    assert rs.error is None, rs.error


def test_membership_change_resumes_after_each_phase_kill(tmp_path):
    """Kill the task at EVERY phase boundary (failpoints at
    add/catch-up/promote/remove) and re-drive: the part converges to
    the target replica set with no orphaned state on the removed
    host."""
    from nebula_tpu.cluster.launcher import LocalCluster
    from nebula_tpu.cluster.repair import (ClientPartOps,
                                           run_membership_change)
    c = LocalCluster(n_meta=1, n_storage=2, n_graph=1,
                     data_dir=str(tmp_path))
    try:
        client = c.client()
        _setup_moving_space(client, c, parts=4)
        store = c.graphds[0].store
        ops = ClientPartOps(store.meta, store.sc)
        addrs = [s.addr for s in c.storage_servers]
        alive = list(addrs)
        sites = ["repair:add_learner", "repair:catchup",
                 "repair:promote", "repair:remove"]
        moved = {}                      # pid → (src, dst)
        for pid, site in enumerate(sites):
            # move each part to the OTHER host, dying at a different
            # phase each time
            src = store.meta.parts_of("mv")[pid][0]
            dst = next(a for a in addrs if a != src)
            moved[pid] = (src, dst)
            with fail.scoped():
                fail.arm(site, "raise(killed-mid-task)")
                with pytest.raises(FailpointError):
                    run_membership_change(ops, "mv", pid, add=dst,
                                          remove=src, alive=alive)
            # re-drive the SAME change from scratch: every phase is
            # idempotent, so the converged result is identical no
            # matter where the first attempt died
            run_membership_change(ops, "mv", pid, add=dst,
                                  remove=src, alive=alive)
            store.meta.refresh(force=True)
            assert store.meta.parts_of("mv")[pid] == [dst]
            assert store.meta.learners_of("mv")[pid] == []
        # no orphaned part state on any removed host
        sid = c.storageds[0].meta.catalog.get_space("mv").space_id
        for pid, (src, dst) in moved.items():
            ss_src = c.storageds[addrs.index(src)]
            # the reconcile that drops a part pops it under the parts
            # lock and stops and clears it after (storage_service.py
            # reconcile_parts), maybe on another thread: wait for both
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                if (sid, pid) not in ss_src.parts and not \
                        ss_src.store.space("mv").parts[pid].vertices:
                    break
                ss_src.reconcile_parts()
                time.sleep(0.1)
            assert (sid, pid) not in ss_src.parts
            assert not ss_src.store.space("mv").parts[pid].vertices
        # data survived the four phase-killed moves
        rs = client.execute("USE mv")
        assert rs.error is None
        rs = client.execute(
            "FETCH PROP ON item 7, 23, 39 YIELD item.x AS x "
            "| ORDER BY $-.x")
        assert rs.error is None, rs.error
        assert rs.data.rows == [[70], [230], [390]]
    finally:
        c.stop()


# ---------------------------------------------------------------------------
# metad-failover false-dead window (satellite)
# ---------------------------------------------------------------------------


def test_fresh_meta_leader_reports_unknown_not_dead(tmp_path):
    """Liveness is leader-local: a fresh metad leader has seen no
    heartbeats, so without the post-election grace every host would
    read dead the instant it takes over.  With heartbeats silenced
    entirely, the new leader must report UNKNOWN (not OFFLINE) until
    one full heartbeat interval of leadership has elapsed — and the
    supervisor must not create any repair plan inside that window."""
    from nebula_tpu.cluster.launcher import LocalCluster
    from nebula_tpu.cluster.rpc import RpcError
    c = LocalCluster(n_meta=3, n_storage=2, n_graph=1,
                     data_dir=str(tmp_path))
    get_config().set_dynamic_many({"heartbeat_interval_secs": 3.0,
                                   "host_hb_expire_secs": 0.4,
                                   "repair_scan_interval_secs": 0.05})
    try:
        client = c.client()
        rs = client.execute(
            "CREATE SPACE fd(partition_num=2, replica_factor=2, "
            "vid_type=INT64)")
        assert rs.error is None, rs.error
        c.reconcile_storage()
        # silence every heartbeat, then depose the leader: the new one
        # must judge the part-map hosts without ANY heartbeat history
        for mc in c.meta_clients:
            mc.stop_heartbeat()
        old = c.meta_leader_index()
        assert old >= 0
        # the grace is one heartbeat interval of leadership, read live:
        # a minute of it, so that however long a busy machine takes to
        # elect the successor and replay its log, the window is still
        # open when the hosts are judged (a 3 s grace under a 2 s wait
        # failed under six xdist workers); it is closed below by
        # shortening the interval, not by waiting it out
        get_config().set_dynamic("heartbeat_interval_secs", 60.0)
        c.stop_metad(old)

        def ask(method):
            """The successor's answer, waited for: on a busy machine the
            leadership can move once more between a look at it and a
            call, and a deposed metad answers `not leader`."""
            deadline = time.monotonic() + 30
            while True:
                idx = c.meta_leader_index()
                if idx >= 0 and idx != old:
                    try:
                        return getattr(c.metads[idx], method)({})
                    except RpcError:
                        pass
                assert time.monotonic() < deadline, "no successor elected"
                time.sleep(0.02)

        def storage_hosts():
            return [h for h in ask("rpc_list_hosts")
                    if h["role"] == "storage"]
        # the new leader may still be applying its log backlog; the
        # part-map hosts must surface (as UNKNOWN) within the grace
        deadline = time.monotonic() + 30
        storage = storage_hosts()
        while len(storage) != 2 and time.monotonic() < deadline:
            time.sleep(0.02)
            storage = storage_hosts()
        assert len(storage) == 2, storage
        assert all(h["status"] == "UNKNOWN" for h in storage), storage
        assert all(not h["alive"] for h in storage), storage
        assert ask("rpc_list_repairs") == []
        # SHOW HOSTS renders the same verdict through the client
        rs = client.execute("SHOW HOSTS STORAGE")
        assert rs.error is None, rs.error
        assert {row[2] for row in rs.data.rows} == {"UNKNOWN"}, \
            rs.data.rows
        # after the grace (one heartbeat interval) + expiry with still
        # no heartbeats, the verdict hardens to OFFLINE
        get_config().set_dynamic("heartbeat_interval_secs", 0.2)
        deadline = time.monotonic() + 30
        hosts = storage_hosts()
        while not all(h["status"] == "OFFLINE" for h in hosts) \
                and time.monotonic() < deadline:
            time.sleep(0.1)
            hosts = storage_hosts()
        assert all(h["status"] == "OFFLINE" for h in hosts), hosts
    finally:
        get_config().set_dynamic_many({"heartbeat_interval_secs": 1.0,
                                       "host_hb_expire_secs": 10.0,
                                       "repair_scan_interval_secs": 0.5})
        c.stop()


# ---------------------------------------------------------------------------
# dynamic catch-up timeout flag (satellite)
# ---------------------------------------------------------------------------


def test_catchup_timeout_flag_is_dynamic():
    """`balance_catchup_timeout_secs` replaced the hardcoded 30s: both
    BALANCE DATA and auto-repair read it per call, and the UPDATE
    CONFIGS multi-key path (set_dynamic_many) retunes it live."""
    from nebula_tpu.cluster.repair import (MembershipError, PartOps,
                                           catchup_timeout_s,
                                           wait_caught_up)
    assert catchup_timeout_s() == 30.0          # the default
    get_config().set_dynamic_many({"balance_catchup_timeout_secs": 0.3})
    try:
        assert catchup_timeout_s() == 0.3

        class DeadOps(PartOps):
            def call_host(self, addr, method, **kw):
                raise ConnectionError("down")
        t0 = time.monotonic()
        with pytest.raises(MembershipError):
            wait_caught_up(DeadOps(), "h1", "sp", 0, ["h0"])
        # honored the dynamic value, not the old 30s hardcode
        assert time.monotonic() - t0 < 5.0
    finally:
        get_config().set_dynamic_many(
            {"balance_catchup_timeout_secs": 30.0})


def test_show_repairs_parses_standalone():
    """SHOW REPAIRS is a first-class statement: parses everywhere,
    empty table on a standalone (cluster-less) store."""
    from nebula_tpu.query.parser import parse
    assert parse("SHOW REPAIRS").kind == "repairs"
