"""A compaction of the delta plane on the CLUSTER feed (PR 37): the
plane's host mirror is folded into a fresh base with no export, writes
that land while it builds are carried over at the swap, a statement that
meets the swap runs again on the new snapshot, a failed compaction is
counted and backs off, and a fold of a few rows compiles nothing.  Also
the pieces under it: the log's generations, `fold_base` against a
rebuild, `adopt`, the padded edge width."""
import os
import sys
import threading
import time

import numpy as np
import pytest

from nebula_tpu.graphstore.schema import PropType
from nebula_tpu.graphstore.delta import (DeltaLog, HostDelta, fold_base,
                                         pad_edge_width, padded_width)
from nebula_tpu.utils.config import get_config
from nebula_tpu.utils.failpoints import fail
from nebula_tpu.utils.stats import stats

tpu = pytest.importorskip("nebula_tpu.tpu")
from nebula_tpu.tpu import TpuRuntime, make_mesh, runtime    # noqa: E402

from test_delta import store_p, wait_for                     # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

KEYS = ("tpu_delta_max_edges", "tpu_delta_compact_watermark")


def moved(before, name):
    """Growth of every counter whose name starts with `name`."""
    now = stats().snapshot()
    return sum(v - before.get(k, 0) for k, v in now.items()
               if k.startswith(name) and isinstance(v, (int, float)))


@pytest.fixture()
def cluster(tmp_path):
    """One storaged with raft and WAL, one graphd holding the runtime,
    eight parts; a plane of 8 edges a (block, part) that compacts at
    half (set before the first pin); ten persons, 1 knows 2 and 3."""
    from nebula_tpu.cluster.launcher import LocalCluster
    fail.reset()
    get_config().set_dynamic_many({"tpu_delta_max_edges": 8,
                                   "tpu_delta_compact_watermark": 0.5})
    rt = TpuRuntime(make_mesh())
    c = LocalCluster(n_meta=1, n_storage=1, n_graph=1,
                     data_dir=str(tmp_path), tpu_runtime=rt)
    try:
        cl = c.client()
        assert cl.execute("CREATE SPACE cc(partition_num=8, replica_factor=1, "
                          "vid_type=INT64)").error is None
        c.reconcile_storage()
        for q in ["USE cc", "CREATE TAG T()", "CREATE EDGE E(w int, f double)",
                  "INSERT VERTEX T() VALUES " + ", ".join(f"{v}:()" for v in range(40)),
                  "INSERT EDGE E(w, f) VALUES 1->2:(1, 0.5), 1->3:(2, 0.25), 2->3:(3, 0.125)"]:
            assert cl.execute(q).error is None, q
        yield c, cl, rt
    finally:
        fail.reset()
        c.stop()
        cfg = get_config()
        with cfg.lock:
            for k in KEYS:
                cfg.dynamic_layer.pop(k, None)


def friends(cl, v=1):
    r = cl.execute(f"GO FROM {v} OVER E YIELD dst(edge) AS d, E.w AS w, E.f AS f")
    assert r.error is None, r.error
    return sorted(map(tuple, r.data.rows))


def add(cl, src, dst, w):
    assert cl.execute(f"INSERT EDGE E(w, f) VALUES {src}->{dst}:({w}, {w / 8!r})").error is None


def fill_past_the_watermark(cl, first=10):
    """Five new edges out of vertex 1: 5 of 8 slots of one buffer."""
    for i in range(5):
        add(cl, 1, first + i, 100 + i)
    return [(first + i, 100 + i, (100 + i) / 8) for i in range(5)]


# -- (a) a write that lands while a compaction builds ----------------------


def test_a_write_acknowledged_during_the_build_is_read_before_and_after_the_swap(cluster):
    c, cl, rt = cluster
    base = [(2, 1, 0.5), (3, 2, 0.25)]
    assert friends(cl) == base
    dev = rt.snapshots["cc"]
    held, go = threading.Event(), threading.Event()
    fail.arm_callable("tpu:compact_swap", lambda i, k: (held.set(), go.wait(30), None)[-1])
    s0 = stats().snapshot()
    new = fill_past_the_watermark(cl)
    assert friends(cl) == sorted(base + new)          # the apply kicks the compaction
    assert held.wait(20), "the compaction never reached its swap"
    # the new base is built (from a copy that lacks this write); the old
    # plane takes it, and a read on the old snapshot serves it
    add(cl, 1, 20, 777)
    late = (20, 777, 777 / 8)
    assert friends(cl) == sorted(base + new + [late])
    assert rt.snapshots["cc"] is dev and moved(s0, "tpu_compactions") == 0
    go.set()
    wait_for(lambda: moved(s0, "tpu_compactions") == 1, msg="the swap")
    fresh = rt.snapshots["cc"]
    assert fresh is not dev and dev.retired
    assert friends(cl) == sorted(base + new + [late]), "an acknowledged write was lost in the swap"
    assert moved(s0, "tpu_compact_carried_keys") >= 1
    assert fresh.delta.host.total_edges() == 2, "only the carried write (its two halves) is left"
    assert fresh.epoch == dev.epoch, "a fold keeps the base's epoch (and its programs)"
    assert moved(s0, "tpu_pins") == 0 and moved(s0, "tpu_host_fallback") == 0
    assert moved(s0, "tpu_compact_build_s.count") == 1 == moved(s0, "tpu_compact_swap_s.count")
    # and the plane goes on taking writes: an overwrite of a folded row, a delete
    add(cl, 1, 10, 5)
    assert cl.execute("DELETE EDGE E 1->11@0").error is None
    want = sorted([r for r in base + new + [late] if r[0] not in (10, 11)] + [(10, 5, 5 / 8)])
    assert friends(cl) == want
    assert rt.snapshots["cc"] is fresh and moved(s0, "tpu_pins") == 0


# -- (b) a statement that meets the swap -----------------------------------


def test_a_statement_queued_across_the_swap_is_served_by_the_new_snapshot(cluster):
    c, cl, rt = cluster
    assert len(friends(cl)) == 2
    new = fill_past_the_watermark(cl)
    s0 = stats().snapshot()
    held, go = threading.Event(), threading.Event()
    # the FIRST dispatch waits in front of the gate, its snapshot pinned
    fail.arm_callable("tpu:dispatch_gate",
                      lambda i, k: (held.set(), go.wait(30), None)[-1] if i == 0 else None)
    got = {}
    cl2 = c.client()
    assert cl2.execute("USE cc").error is None
    t = threading.Thread(target=lambda: got.update(rows=friends(cl2)), daemon=True)
    t.start()
    assert held.wait(20), "the statement never reached the gate"
    wait_for(lambda: moved(s0, "tpu_compactions") == 1, msg="the swap behind the statement")
    go.set()
    t.join(30)
    assert got.get("rows") == sorted([(2, 1, 0.5), (3, 2, 0.25)] + new)
    assert moved(s0, "tpu_stmt_retired_retries") == 1
    assert moved(s0, "tpu_host_fallback") == 0, "a statement caught by the swap went to the host"
    assert moved(s0, "tpu_pins") == 0


# -- (c) a compaction that raises ------------------------------------------


def test_a_failed_compaction_is_counted_and_backs_off(cluster, monkeypatch, caplog):
    c, cl, rt = cluster
    assert len(friends(cl)) == 2
    dev = rt.snapshots["cc"]
    calls = []

    def boom(*a, **kw):
        calls.append(1)
        raise RuntimeError("no room")
    monkeypatch.setattr(runtime, "fold_base", boom)
    s0 = stats().snapshot()
    new = fill_past_the_watermark(cl)
    with caplog.at_level("WARNING", logger="nebula_tpu.tpu.runtime"):
        assert len(friends(cl)) == 7
        wait_for(lambda: not dev._compacting and calls, msg="the failed compaction")
    assert moved(s0, "tpu_compaction_failures") == 2          # the total and its cause
    assert stats().snapshot()["tpu_compaction_failures_by_cause{cause=RuntimeError}"] >= 1
    assert sum("compaction of cc failed" in r.message for r in caplog.records) == 1
    assert moved(s0, "tpu_compactions") == 0 and rt.snapshots["cc"] is dev
    # the next apply, at once, does not try again
    add(cl, 2, 30, 9)
    assert friends(cl, 2) == [(3, 3, 0.125), (30, 9, 9 / 8)]
    time.sleep(0.1)
    assert len(calls) == 1 and dev._compact_not_before > time.monotonic()
    # once the back-off has run, it does, and succeeds
    monkeypatch.undo()
    dev._compact_not_before = 0.0
    add(cl, 2, 31, 10)
    assert len(friends(cl, 2)) == 3
    wait_for(lambda: moved(s0, "tpu_compactions") == 1, msg="the retry")
    assert friends(cl) == sorted([(2, 1, 0.5), (3, 2, 0.25)] + new)
    assert moved(s0, "tpu_pins") == 0


# -- (d) the swap compiles nothing -----------------------------------------


def test_a_swap_after_a_few_folded_rows_compiles_nothing(cluster):
    from benchmarks.lib.compiles import CompileWatch
    c, cl, rt = cluster
    assert len(friends(cl)) == 2                  # the plane empty
    add(cl, 1, 30, 1)
    assert len(friends(cl)) == 3                  # the plane live
    widths = {bk: b.nbr.shape for bk, b in rt.snapshots["cc"].host.blocks.items()}
    watch = CompileWatch()
    k0 = watch.compiles
    s0 = stats().snapshot()
    new = fill_past_the_watermark(cl)
    assert len(friends(cl)) == 8
    wait_for(lambda: moved(s0, "tpu_compactions") == 1, msg="the swap")
    after = rt.snapshots["cc"]
    assert {bk: b.nbr.shape for bk, b in after.host.blocks.items()} == widths
    assert after.delta.host.dcap == 8
    assert len(friends(cl)) == 8                  # empty again, on the new base
    add(cl, 1, 31, 2)
    assert len(friends(cl)) == 9                  # and live again
    assert watch.compiles == k0, "the swap brought a backend compile"
    assert moved(s0, "tpu_pins") == 0
    assert new[0] in friends(cl)


# -- the pieces ------------------------------------------------------------


def test_trim_keeps_a_key_noted_again_since_it_was_handed_out():
    log = DeltaLog()
    k1, k2 = ("e", "E", 1, 2, 0), ("e", "E", 1, 3, 0)
    log.note(k1)
    log.note(k2)
    handed = log.records()
    log.note(k1)                    # the same write, acknowledged now
    log.trim(handed)
    assert list(log.keys) == [k1], "a key noted during an apply must outlive its trim"
    log.trim(log.records())
    assert not log.keys


def test_a_cluster_write_is_noted_again_once_acknowledged(cluster):
    """The race eight writers open: an apply that re-reads a key before
    its write commits must not be the one that drops it."""
    c, cl, rt = cluster
    assert len(friends(cl)) == 2
    store = c.graphds[0].store
    log = store._delta_logs["cc"]
    seen = []
    real = store._write_many

    def spy(space, by_part):
        # what a concurrent apply is handed while the write is in flight
        seen.append(dict(log.keys))
        return real(space, by_part)
    store._write_many = spy
    try:
        add(cl, 1, 33, 4)
    finally:
        store._write_many = real
    key = ("e", "E", 1, 33, 0)
    assert key in seen[0], "the key is noted before its write ships"
    assert log.keys[key] > seen[0][key], "and again, a generation on, once acknowledged"
    log.trim(seen[0])
    assert key in log.keys
    assert (33, 4, 0.5) in friends(cl)


def test_padded_width_leaves_a_capacity_free_and_rounds_to_it():
    assert padded_width(0, 1024) == 1024
    assert padded_width(1, 1024) == 2048
    assert padded_width(34_000, 1024) == 35_840
    assert padded_width(35_867, 1024) == 37_888 == padded_width(36_000, 1024)
    for rows in (0, 5, 1023, 1024, 99_999):
        w = padded_width(rows, 1024)
        assert w % 1024 == 0 and 1024 <= w - rows < 2048


@pytest.mark.parametrize("parts", [1, 2])
def test_fold_base_is_the_rebuild(parts):
    """Base plus plane folded equals the snapshot a rebuild exports:
    same rows in the same slots, block by block, after inserts, an
    overwrite, a delete and a delete of a fresh row."""
    from nebula_tpu.graphstore.csr import build_snapshot
    get_config().set_dynamic_many({"tpu_delta_max_edges": 64,
                                   "tpu_delta_compact_watermark": 2.0})
    try:
        st = store_p(parts, seed=21)
        rt = TpuRuntime(make_mesh(parts))
        dev = rt.pin(st, "g")
        assert all(b.nbr.shape[1] == padded_width(int(b.indptr[:, -1].max()), 64)
                   for b in dev.host.blocks.values())
        for i in range(12):
            st.insert_edge("g", 1 + i % 3, "knows", (7 * i) % 90, 40 + i % 2,
                           {"w": i, "f": i / 4, "tag": "z"})
        st.insert_edge("g", 1, "knows", 0, 40, {"w": 99, "f": 2.5, "tag": "o"})     # overwrite
        (victim,) = [k for k in st.space("g").parts[st.space("g").part_of(5)]
                     .out_edges.get(5, {}).get("knows", {})][:1] or [None]
        if victim is not None:
            st.delete_edge("g", 5, "knows", victim[1], victim[0])
        st.delete_edge("g", 2, "knows", 7, 41)                                     # a fresh row
        assert rt.pin(st, "g") is dev and dev.delta.host.total_edges() > 0
        hd = dev.delta.host
        folded = fold_base(hd.snap, *hd.freeze(), hd.dcap)
        want = build_snapshot(st, "g", vmax_extra=int(get_config().get("tpu_delta_vmax_slack")))
        assert folded.epoch == dev.epoch and folded.tags is hd.snap.tags
        for bk, wb in want.blocks.items():
            fb = folded.blocks[bk]
            assert fb.nbr.shape == dev.host.blocks[bk].nbr.shape, "the padded width is kept"
            n = wb.indptr[:, -1]
            assert (fb.indptr == wb.indptr).all(), bk
            for p in range(parts):
                k = int(n[p])
                assert (fb.nbr[p, :k] == wb.nbr[p, :k]).all() and (fb.nbr[p, k:] == -1).all()
                assert (fb.rank[p, :k] == wb.rank[p, :k]).all()
                for name in wb.props:
                    got, exp = fb.props[name][p, :k], wb.props[name][p, :k]
                    if wb.prop_types[name] == PropType.STRING:      # codes of two pools
                        got = [folded.pool.decode(int(x)) for x in got]
                        exp = [want.pool.decode(int(x)) for x in exp]
                        assert got == exp, (bk, name)
                        continue
                    assert np.array_equal(got, exp, equal_nan=exp.dtype.kind == "f"), (bk, name)
        # adopt: what is applied after the copy lands in the new plane, no more
        frozen = hd.freeze()
        st.insert_edge("g", 3, "knows", 8, 77, {"w": 5, "f": .5, "tag": "n"})
        st.delete_edge("g", 1, "knows", 0, 40)
        keys = st.delta_records("g")[0]
        assert rt.pin(st, "g") is dev
        new_hd = HostDelta(fold_base(hd.snap, *frozen, hd.dcap), hd.dcap)
        changed = new_hd.adopt(hd, keys, st.delta_reader("g").dense_of)
        assert changed and new_hd.total_edges() == 2 == new_hd.total_tombs()    # two halves an edge
    finally:
        cfg = get_config()
        with cfg.lock:
            for k in KEYS:
                cfg.dynamic_layer.pop(k, None)


def test_fold_base_grows_a_width_that_no_longer_fits():
    from nebula_tpu.graphstore.csr import CsrBlock, CsrSnapshot
    blk = CsrBlock("E", "out", np.asarray([[0, 2, 2]], np.int32),
                   np.asarray([[0, 1]], np.int32), np.zeros((1, 2), np.int32),
                   {"w": np.asarray([[5, 6]], np.int64)}, {})
    snap = CsrSnapshot("s", 3, 1, 2, np.asarray([2], np.int32), {("E", "out"): blk},
                       dense_to_vid=[0, 1])
    ins = {("E", "out"): [{(1, 0, 0): {"w": 7}}]}
    out = fold_base(snap, ins, {("E", "out"): [set()]}, 4).blocks[("E", "out")]
    assert out.nbr.shape == (1, padded_width(3, 4)) == (1, 8)
    assert out.indptr.tolist() == [[0, 2, 3]] and out.nbr[0, :3].tolist() == [0, 1, 0]
    assert out.props["w"][0, :3].tolist() == [5, 6, 7]
    assert pad_edge_width(snap, 4).blocks[("E", "out")].nbr.shape == (1, 8)
