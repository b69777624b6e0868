"""Exactly-once storage writes (ISSUE 5 tentpole).

Every storage.write carries a (writer_id, seq) idempotency token; the
part keeps a raft-replicated dedup window of applied tokens.  A re-sent
request — the client walked replicas after a lost reply — returns its
recorded outcome instead of double-applying, which is what flips the
old mid-call abort (`... not retried (non-idempotent)`) into a safe
retry.
"""
import threading
import time
from collections import OrderedDict

import pytest

from nebula_tpu.cluster.launcher import LocalCluster
from nebula_tpu.cluster.rpc import reset_breakers
from nebula_tpu.cluster.storage_client import StorageClient, StorageError
from nebula_tpu.core.wire import to_wire
from nebula_tpu.graphstore.store import DEDUP_WINDOW, GraphStore
from nebula_tpu.utils.failpoints import fail
from nebula_tpu.utils.stats import stats


@pytest.fixture()
def clean_faults():
    fail.reset()
    reset_breakers()
    stats().reset()
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

    run("CREATE SPACE eo(partition_num=4, replica_factor=2, "
        "vid_type=INT64)")
    c.reconcile_storage()
    run("USE eo")
    run("CREATE TAG Person(name string, age int)")
    run("CREATE EDGE KNOWS(w int)")
    run('INSERT VERTEX Person(name, age) VALUES 1:("ann",30), 2:("bob",25)')
    c.run = run
    yield c
    c.stop()


# -- store-level dedup window ----------------------------------------------


def test_dedup_window_record_seen_and_eviction():
    st = GraphStore()
    st.create_space("s", partition_num=1, vid_type="INT64")
    assert st.dedup_seen("s", 0, "w", 1) is None
    st.dedup_record("s", 0, "w", 1, {"n": 2, "err": None})
    assert st.dedup_seen("s", 0, "w", 1) == {"n": 2, "err": None}
    # overflow evicts in insertion order, deterministically
    for i in range(2, DEDUP_WINDOW + 2):
        st.dedup_record("s", 0, "w", i, {"n": 1, "err": None})
    assert st.dedup_seen("s", 0, "w", 1) is None          # evicted
    assert st.dedup_seen("s", 0, "w", DEDUP_WINDOW + 1) is not None


def test_dedup_window_rides_part_state_snapshot():
    st = GraphStore()
    st.create_space("s", partition_num=1, vid_type="INT64")
    st.dedup_record("s", 0, "w", 7, {"n": 3, "err": "boom"})
    payload = st.export_part_state("s", 0)
    st2 = GraphStore()
    st2.create_space("s", partition_num=1, vid_type="INT64")
    st2.install_part_state("s", 0, payload)
    assert st2.dedup_seen("s", 0, "w", 7) == {"n": 3, "err": "boom"}
    # window ORDER survives the roundtrip (eviction order is state)
    sd = st2.space("s")
    assert isinstance(sd.parts[0].applied_writes, OrderedDict)


# -- dbatch apply gate ------------------------------------------------------


def test_duplicate_dbatch_apply_skips(cluster, clean_faults):
    """The replicated apply gate: a second dbatch with the same
    (writer, seq) must NOT re-apply — proven by giving the duplicate a
    DIFFERENT payload and observing the original's effect survive."""
    sc = StorageClient(cluster.meta_clients[0])
    pid = sc.part_of("eo", 1)
    # apply on the storaged LEADING the part: leadership is election-
    # random, and the FETCH below reads through the leader — a side-
    # applied write on a lagged follower would be invisible to it
    sid = cluster.storageds[0].meta.catalog.get_space("eo").space_id
    # between two leaders (an election on a busy machine) nobody leads:
    # wait for one
    deadline = time.monotonic() + 20
    while True:
        ss = next((s for s in cluster.storageds if (sid, pid) in s.parts
                   and s.parts[(sid, pid)].is_leader()), None)
        if ss is not None:
            break
        assert time.monotonic() < deadline, "no storaged leads the part"
        time.sleep(0.02)
    ss._apply_dbatch("eo", pid, "wdup", 1,
                     [["upd_vertex", 1, "Person", {"age": 77}]])
    before = stats().snapshot().get("storage_write_dedup_apply_skips", 0)
    ss._apply_dbatch("eo", pid, "wdup", 1,
                     [["upd_vertex", 1, "Person", {"age": 78}]])
    after = stats().snapshot().get("storage_write_dedup_apply_skips", 0)
    assert after == before + 1
    assert ss.store.dedup_seen("eo", pid, "wdup", 1) == \
        {"n": 1, "err": None}
    rs = cluster.run("FETCH PROP ON Person 1 YIELD Person.age AS a")
    assert rs.data.rows == [[77]], "duplicate dbatch re-applied!"


def test_dbatch_records_error_outcome(cluster, clean_faults):
    ss = cluster.storageds[0]
    with pytest.raises(ValueError):
        ss._apply_dbatch("eo", 0, "werr", 1, [["no_such_op"]])
    rec = ss.store.dedup_seen("eo", 0, "werr", 1)
    assert rec is not None and "no_such_op" in rec["err"]


def test_duplicate_dbatch_reraises_recorded_error(cluster, clean_faults):
    """A duplicate of a FAILED dbatch must fail identically — a silent
    skip would ack the retry of a write whose original apply failed."""
    ss = cluster.storageds[0]
    with pytest.raises(ValueError, match="no_such_op"):
        ss._apply_dbatch("eo", 0, "werr2", 1, [["no_such_op"]])
    before = stats().snapshot().get("storage_write_dedup_apply_skips", 0)
    with pytest.raises(ValueError, match="no_such_op"):
        ss._apply_dbatch("eo", 0, "werr2", 1, [["no_such_op"]])
    after = stats().snapshot().get("storage_write_dedup_apply_skips", 0)
    assert after == before + 1      # skipped, not re-applied — but failed


# -- end-to-end: lost reply → replica-walk retry → dedup hit ---------------


def _arm_reply_loss_once(key="storage.write|ok"):
    """Kill the reply of the next SUCCESSFUL storage.write — the
    handler ran, the write committed, the ack is lost (killing an error
    reply would inject a different, weaker fault)."""
    state = {"fired": False}

    def decide(idx, k):
        if state["fired"] or k != key:
            return None
        state["fired"] = True
        return ("raise", "reply dropped")

    fail.arm_callable("rpc:server_reply", decide)
    return state


def test_acked_write_exactly_once_after_lost_reply(cluster, clean_faults):
    """The headline flip: the server applies a write, the reply is lost
    (connection killed post-dispatch), the client re-sends the SAME
    token — the statement still acks, the write lands exactly once."""
    state = _arm_reply_loss_once()
    rs = cluster.run('INSERT VERTEX Person(name, age) VALUES 50:("eve",8)')
    assert rs.error is None
    assert state["fired"], "failpoint never fired — test proved nothing"
    snap = stats().snapshot()
    dedup = snap.get("storage_write_dedup_hits", 0) + \
        snap.get("storage_write_dedup_apply_skips", 0)
    assert dedup >= 1, f"re-send was not deduplicated: {snap}"
    rs = cluster.run("FETCH PROP ON Person 50 YIELD Person.name AS n, "
                     "Person.age AS a")
    assert rs.data.rows == [["eve", 8]]


def test_update_not_lost_after_reply_loss(cluster, clean_faults):
    """Same flip for UPDATE: the acked new value survives the re-send
    (without dedup the duplicate would be invisible here — this guards
    the ack itself: the statement must succeed, not abort mid-call)."""
    cluster.run('INSERT VERTEX Person(name, age) VALUES 60:("fay",1)')
    _arm_reply_loss_once()
    rs = cluster.run("UPDATE VERTEX ON Person 60 SET age = age + 1")
    assert rs.error is None
    rs = cluster.run("FETCH PROP ON Person 60 YIELD Person.age AS a")
    assert rs.data.rows == [[2]]


def test_untokened_write_still_aborts_mid_call(cluster, clean_faults):
    """The at-least-once gate is unchanged for writes WITHOUT a dedup
    token (raw storage.write callers): a mid-call death must surface,
    not silently re-send."""
    sc = StorageClient(cluster.meta_clients[0])
    _arm_reply_loss_once()
    cmd = ["vertex", 70, "Person", 0, {"name": "gus", "age": 3}]
    with pytest.raises(StorageError, match="not retried"):
        sc._call_part("eo", sc.part_of("eo", 70), "storage.write",
                      {"cmds": [to_wire(cmd)],
                       "cat_ver": cluster.meta_clients[0].version})


def test_tokened_retry_survives_leader_restart_window(cluster,
                                                      clean_faults):
    """Reply loss + a racing second statement: both ack, both land,
    ordering preserved (the dedup window keys on (writer, seq) so the
    sibling write is untouched)."""
    state = _arm_reply_loss_once()
    done = {}

    def other():
        done["rs"] = cluster.run(
            'INSERT VERTEX Person(name, age) VALUES 81:("ian",4)')

    t = threading.Thread(target=other)
    t.start()
    rs = cluster.run('INSERT VERTEX Person(name, age) VALUES 80:("hal",2)')
    t.join()
    assert rs.error is None and done["rs"].error is None
    assert state["fired"]
    rows = cluster.run("FETCH PROP ON Person 80, 81 YIELD Person.name "
                       "AS n").data.rows
    assert sorted(r[0] for r in rows) == ["hal", "ian"]
