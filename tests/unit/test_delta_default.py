"""The delta plane at DEFAULT flags (PR 32): a pin arms it wherever the
store feeds one, at a capacity worked out at pin time; an armed plane
with nothing in it costs a read nothing (same rows, same fetched
columns, no merge stage run); a write is served fresh without a re-pin,
through a local store and through a cluster; the apply has a span, a
phase and series of its own; `pin_prebuilt` arms nothing and an explicit
0 still turns the plane off."""
import numpy as np
import pytest

from nebula_tpu.exec.engine import QueryEngine
from nebula_tpu.utils import trace
from nebula_tpu.utils.config import get_config
from nebula_tpu.utils.stats import stats

tpu = pytest.importorskip("nebula_tpu.tpu")
from nebula_tpu.graphstore.delta import HostDelta, pow2      # noqa: E402
from nebula_tpu.tpu import (TpuRuntime, fetch, make_mesh,    # noqa: E402
                            runtime)
from nebula_tpu.tpu.fetch import Fetcher                     # noqa: E402

from test_delta import dev_rows, host_rows, store_p          # noqa: E402

FLAG = "tpu_delta_max_edges"
REAL_FETCH = Fetcher.fetch


@pytest.fixture()
def flags():
    """Default flags going in; whatever a test sets is taken back."""
    cfg = get_config()
    with cfg.lock:
        assert FLAG not in cfg.dynamic_layer
    yield cfg
    with cfg.lock:
        for k in (FLAG, "tpu_delta_compact_watermark", "tpu_hbm_limit_bytes",
                  "tpu_degree_split_threshold"):
            cfg.dynamic_layer.pop(k, None)


def snb_engine(rt, persons=120, degree=5, seed=7):
    """The served cells' schema and generator through a local store."""
    import os
    import sys
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.lib import loader
    from nebula_tpu.graphstore.store import GraphStore
    t = loader.module("reference/generators", "snb_tables").generate(
        {"persons": persons, "degree": degree}, seed)
    eng = QueryEngine(GraphStore(), tpu_runtime=rt)
    s = eng.new_session()

    def ex(q):
        r = eng.execute(s, q)
        assert r.error is None, f"{q[:80]} -> {r.error}"
        return r
    ex("CREATE SPACE snb(partition_num=2, vid_type=INT64)")
    ex("USE snb")
    ex("CREATE TAG Person(age int, name string)")
    for et in ("KNOWS", "LIKES"):
        ex(f"CREATE EDGE {et}(w int, f double)")
    ex("INSERT VERTEX Person(age, name) VALUES " + ", ".join(
        f'{v}:({a}, "{n}")' for v, (a, n) in enumerate(
            zip(t["vertex"]["age"].tolist(), t["vertex"]["name"]))))
    for et, e in t["edges"].items():
        ex(f"INSERT EDGE {et}(w, f) VALUES " + ", ".join(
            f"{a}->{b}:({w}, {f!r})" for a, b, w, f in zip(
                e["src"].tolist(), e["dst"].tolist(), e["w"].tolist(), e["f"].tolist())))
    return eng, s, ex


# -- arming ---------------------------------------------------------------


def test_a_pin_at_default_flags_arms_the_plane_by_the_rule(flags):
    assert int(flags.get(FLAG)) < 0
    st = store_p(2, seed=11)
    rt = TpuRuntime(make_mesh(2))
    dev = rt.pin(st, "g")
    assert dev.delta is not None and dev.delta.host.total_edges() == 0
    width = max(b.nbr.shape[1] for b in dev.host.blocks.values())
    assert dev.delta.host.dcap == max(pow2(-(-width // 64)), 1024) == 1024
    assert dev.delta.host.tcap == dev.delta.host.dcap
    # the slack rows a new vertex needs came with the snapshot
    assert dev.vmax >= 90 // 2 + int(flags.get("tpu_delta_vmax_slack"))
    assert stats().snapshot()["tpu_delta_capacity_edges"] == 1024


@pytest.mark.parametrize("width,want", [(0, 1024), (1000, 1024), (65536, 1024),
                                        (65537, 2048), (1 << 22, 1 << 16),
                                        (50_331_648, 1 << 20)])
def test_capacity_is_a_power_of_two_of_the_parts_edge_width(width, want):
    import types
    rt = TpuRuntime(make_mesh(1))
    blk = types.SimpleNamespace(nbr=np.empty((8, width), np.int32), prop_types={})
    snap = types.SimpleNamespace(blocks={("E", "out"): blk}, num_parts=8, hub_dense=None)
    assert rt._delta_capacity(snap, None) == want


def test_capacity_is_held_to_a_share_of_the_hbm_headroom():
    import types
    rt = TpuRuntime(make_mesh(1))
    blk = types.SimpleNamespace(nbr=np.empty((8, 1 << 22), np.int32), prop_types={})
    snap = types.SimpleNamespace(blocks={("E", "out"): blk, ("E", "in"): blk},
                                 num_parts=8, hub_dense=None)
    free = rt._delta_capacity(snap, None)
    assert free == 1 << 16
    slot = HostDelta(snap, 1).nbytes()              # every block, every part, one slot
    assert slot == 2 * 8 * (4 * 3 + 1 + 4)
    # all delta buffers of the device fit 1/64 of the headroom, halving until they do
    for headroom in (slot * free * 64, slot * free * 64 - 1, slot * 64 * 1024, 64 * slot, 0):
        cap = rt._delta_capacity(snap, headroom)
        assert cap == pow2(cap) and (cap * slot <= headroom // 64 or cap == 1)
        assert cap == free or 2 * cap * slot > headroom // 64
    assert rt._delta_capacity(snap, slot * free * 64) == free
    assert rt._delta_capacity(snap, slot * free * 64 - 1) == free // 2


def test_an_explicit_value_fixes_the_capacity_and_zero_turns_the_plane_off(flags):
    st = store_p(1, seed=12)
    flags.set_dynamic(FLAG, 48)
    dev = TpuRuntime(make_mesh(1)).pin(st, "g")
    assert dev.delta.host.dcap == 64
    flags.set_dynamic(FLAG, 0)
    rt = TpuRuntime(make_mesh(1))
    dev = rt.pin(st, "g")
    assert dev.delta is None and rt._delta_sig(dev) is None


def test_pin_prebuilt_and_a_hub_split_snapshot_arm_nothing(flags):
    from nebula_tpu.graphstore.csr import build_snapshot
    st = store_p(1, seed=13)
    rt = TpuRuntime(make_mesh(1))
    dev = rt.pin_prebuilt(build_snapshot(st, "g"))
    assert dev.delta is None
    flags.set_dynamic("tpu_degree_split_threshold", 4)
    dev = TpuRuntime(make_mesh(1)).pin(st, "g")
    assert dev.host.hub_dense is not None and dev.delta is None


def test_a_store_that_feeds_no_delta_arms_nothing(flags):
    st = store_p(1, seed=14)

    class NoFeed:
        """The store without its dirty-key log and key re-reader."""
        def __init__(self, inner):
            self.inner = inner

        def __getattr__(self, k):
            if k.startswith("delta_"):
                raise AttributeError(k)
            return getattr(self.inner, k)
    dev = TpuRuntime(make_mesh(1)).pin(NoFeed(st), "g")
    assert dev.delta is None


# -- an armed, empty plane costs a read nothing ---------------------------


NBENCH_GO = ["GO 1 STEPS FROM $v OVER KNOWS YIELD dst(edge) AS d, KNOWS.w AS w",
             "GO 2 STEPS FROM $v OVER KNOWS YIELD dst(edge) AS d, KNOWS.f AS f",
             "GO 3 STEPS FROM $v OVER KNOWS WHERE KNOWS.w > 50 YIELD dst(edge) AS d, KNOWS.w AS w",
             "GO 3 STEPS FROM $v OVER * YIELD dst(edge) AS d"]
SNB_PATH = ["MATCH (p:Person)-[:KNOWS]->(f)-[:KNOWS]->(ff:Person) WHERE id(p) IN [$v] "
            "AND ff.Person.age > 30 RETURN id(ff) AS v, count(*) AS c",
            "MATCH (a:Person)-[e:KNOWS*1..4]->(b) WHERE id(a) IN [$v] RETURN count(*) AS paths",
            "FIND SHORTEST PATH FROM $v TO 17 OVER KNOWS UPTO 4 STEPS YIELD path AS p",
            "GET SUBGRAPH 2 STEPS FROM $v OUT KNOWS YIELD VERTICES AS v, EDGES AS e"]


def _served(monkeypatch, flag):
    """Every statement shape of the two served mixes from three sources:
    (rows per statement, the columns each fetch brought)."""
    cfg = get_config()
    if flag is not None:
        cfg.set_dynamic(FLAG, flag)
    fetched = []
    real = REAL_FETCH

    def fetch(self, res, key, fetch_keys, info):
        fetched.append(None if fetch_keys is None else tuple(sorted(fetch_keys)))
        return real(self, res, key, fetch_keys, info)
    monkeypatch.setattr(Fetcher, "fetch", fetch)
    rt = TpuRuntime(make_mesh(1))
    eng, s, ex = snb_engine(rt)
    runs0 = stats().snapshot().get("tpu_kernel_runs", 0)
    rows = []
    for text in NBENCH_GO + SNB_PATH:
        for v in (3, 40, 77):
            rows.append(sorted(map(repr, ex(text.replace("$v", str(v))).data.rows)))
    assert stats().snapshot().get("tpu_kernel_runs", 0) - runs0 >= len(rows)
    dev = rt.snapshots["snb"]
    return rows, fetched, dev


def test_an_armed_empty_plane_returns_the_rows_and_fetches_the_columns_of_the_plane_off(
        flags, monkeypatch):
    on_rows, on_cols, dev = _served(monkeypatch, None)
    assert dev.delta is not None and dev.delta.host.total_edges() == 0
    assert not any(any(e["rows"]) for e in dev.delta.view[1].values())
    off_rows, off_cols, dev0 = _served(monkeypatch, 0)
    assert dev0.delta is None
    assert on_rows == off_rows and sum(map(len, on_rows)) > 100
    assert on_cols == off_cols          # no identity column forced into a fetch
    assert any(c is not None and "src" not in c for c in on_cols)


def test_an_empty_planes_program_runs_no_merge_stage_and_counts_no_more_chunks(flags):
    """The engagement counters of an armed, empty plane are the plane
    off's: the compaction its capture would need is not run (nor
    budgeted) while it holds nothing, and is once it holds a row."""
    st = store_p(1, seed=21, n=200, avg_deg=6)
    vids = list(range(0, 200, 3))
    out = {}
    for flag in (0, None):
        with get_config().lock:
            get_config().dynamic_layer.pop(FLAG, None)
        if flag is not None:
            get_config().set_dynamic(FLAG, flag)
        rt = TpuRuntime(make_mesh(1))
        small = runtime.build_traverse_fn
        try:
            runtime.build_traverse_fn = lambda *a, **kw: small(*a, chunk=64, **kw)
            rows, ts = rt.traverse(st, "g", vids, ["knows"], "out", 2)
            out[flag] = (sorted(map(repr, rows)), ts.chunks_run, ts.chunks_budget)
            if flag is None:
                st.insert_edge("g", vids[0], "knows", 199, 5, {"w": 1, "f": .5, "tag": "x"})
                rows, ts = rt.traverse(st, "g", vids, ["knows"], "out", 2)
                live = (len(rows), ts.chunks_run, ts.chunks_budget)
        finally:
            runtime.build_traverse_fn = small
    assert out[None] == out[0] and out[0][2] > 0
    # a row in the plane: the capture is compacted, by need
    assert live[0] >= len(out[0][0]) and live[2] > out[0][2] and live[1] > out[0][1]


def test_the_planes_tail_does_not_move_a_capture_to_the_other_fetch(flags):
    """`snb-path`'s frames are 65,536 slots a part: with the plane's
    1,024 behind them they still leave the device as one slice a row
    (`_Heads`), as without a plane; pieces start at a budget of twice
    that."""
    import jax.numpy as jnp
    for width, taker in ((1 << 16, fetch._Heads), ((1 << 16) + 1024, fetch._Heads),
                         ((1 << 16) + (1 << 15), fetch._Heads), (1 << 17, fetch._Pieces),
                         ((1 << 17) + 1024, fetch._Pieces)):
        cap = {"dst": jnp.zeros((1, 1, width), jnp.int32)}
        assert type(fetch._taker(cap)) is taker, width
    # the slice sizes of a widened capture: the powers of two, then the whole width
    heads = fetch._taker({"dst": jnp.zeros((1, 1, 8192 + 1024), jnp.int32)})
    assert [heads._k(n) for n in (1, 200, 8192, 8193, 9216)] == [128, 256, 8192, 9216, 9216]


def test_bfs_keeps_its_direction_switch_while_the_plane_is_empty(flags):
    """Direction-optimising BFS is in the program whether or not a plane
    is armed; a level goes top-down once the plane holds something, and
    the distances stay the host's either way."""
    st = store_p(1, seed=23, n=150, avg_deg=5)
    rt = TpuRuntime(make_mesh(1))
    keys = []
    real = rt._escalate

    def esc(dev, dense, key_fn=None, **kw):
        keys.append(key_fn((64,)))
        return real(dev, dense, key_fn=key_fn, **kw)
    rt._escalate = esc
    d0, _ = rt.bfs(st, "g", [1], ["knows"], "out", 4)
    assert keys[-1][8] is True                      # have_rev, with the plane armed
    st.insert_edge("g", 1, "knows", 149, 0, {"w": 1, "f": .5, "tag": "x"})
    d1, _ = rt.bfs(st, "g", [1], ["knows"], "out", 4)
    dev = rt.snapshots["g"]
    assert dev.delta.host.total_edges() == 2        # the out row and its in twin
    sd = st.space("g")
    at = sd.dense_id(149)
    assert d1[at % 1, at // 1] == 1 and d0[at % 1, at // 1] != 1
    get_config().set_dynamic(FLAG, 0)
    d_off, _ = TpuRuntime(make_mesh(1)).bfs(st, "g", [1], ["knows"], "out", 4)
    n = min(d1.shape[1], d_off.shape[1])
    assert (d1[:, :n] == d_off[:, :n]).all()


# -- a fresh read after a write, at default flags --------------------------


@pytest.mark.parametrize("parts", [1, 2])
def test_writes_ride_the_plane_at_default_flags(flags, parts):
    st = store_p(parts, seed=31)
    rt = TpuRuntime(make_mesh(parts))
    dev = rt.pin(st, "g")
    s0 = stats().snapshot()
    rng = np.random.default_rng(5)
    for i in range(24):
        v = int(rng.integers(90))
        if i % 4 == 3:
            edges = [(s_, r, d) for (s_, _e, r, d, _p) in st.scan_edges("g", "knows")]
            s_, r, d = edges[int(rng.integers(len(edges)))]
            if i % 8 == 7:
                st.delete_edge("g", s_, "knows", d, r)
            else:
                st.insert_edge("g", s_, "knows", d, r, {"w": 1000 + i, "f": .25, "tag": "o"})
            v = s_
        else:
            st.insert_edge("g", v, "knows", int(rng.integers(90)), 100 + i,
                           {"w": 1000 + i, "f": float(rng.random()), "tag": "n"})
        assert dev_rows(rt, st, [v], steps=1) == host_rows(st, "g", [v], steps=1)
    s1 = stats().snapshot()
    assert rt.snapshots["g"] is dev
    assert s1.get("tpu_pins", 0) == s0.get("tpu_pins", 0)
    assert s1["tpu_repin_avoided"] - s0.get("tpu_repin_avoided", 0) == 24
    assert s1["tpu_delta_keys.count"] - s0.get("tpu_delta_keys.count", 0) == 24
    assert s1["tpu_delta_keys.sum"] - s0.get("tpu_delta_keys.sum", 0) == 24
    assert s1["tpu_delta_apply_s.count"] - s0.get("tpu_delta_apply_s.count", 0) == 24
    assert s1["tpu_delta_put_s.sum"] < s1["tpu_delta_apply_s.sum"]
    assert 0 < s1["tpu_delta_fill_ratio"] < 0.05
    # the apply's wait for the gate is its own series: a re-pin's did not move
    assert s1["tpu_delta_gate_wait_us.count"] - s0.get("tpu_delta_gate_wait_us.count", 0) == 24
    assert s1.get("tpu_repin_wait_us.count", 0) == s0.get("tpu_repin_wait_us.count", 0)


def test_the_apply_has_a_span_with_children_and_a_phase_of_its_own(flags):
    rt = TpuRuntime(make_mesh(1))
    eng, s, ex = snb_engine(rt)
    q = "GO 1 STEPS FROM 3 OVER KNOWS YIELD dst(edge) AS d, KNOWS.w AS w, KNOWS.f AS f"
    ex(q)
    p0 = stats().snapshot()
    ex("INSERT EDGE KNOWS(w, f) VALUES 3->99:(1234, 0.1)")
    tg = eng.statement_trace(s.id, q)
    with tg:
        rs = eng.execute(s, q, trace_root=tg)
    assert rs.error is None
    assert [1234] == [r[1] for r in rs.data.rows if r[0] == 99]
    spans = trace.trace_store().get(tg.trace_id)["spans"]
    by_sid = {sp["sid"]: sp for sp in spans}
    apply = [sp for sp in spans if sp["name"] == "tpu:delta_apply"]
    assert len(apply) == 1
    kids = sorted(sp["name"] for sp in spans if by_sid.get(sp["psid"]) is apply[0])
    assert kids == ["device:delta_put", "tpu:delta_census", "tpu:delta_census",
                    "tpu:delta_gate", "tpu:delta_reread"]
    for name in kids + ["tpu:delta_apply"]:
        assert trace.phase_of(name) == "delta_apply"
    assert trace.phase_of("device:put") == "put" and "delta_apply" in trace.PHASES
    p1 = stats().snapshot()
    key = "stmt_phase_us{phase=delta_apply}"
    assert p1[key] - p0.get(key, 0) > 0
    assert p1["stmt_phase_n{phase=delta_apply}"] - p0.get("stmt_phase_n{phase=delta_apply}", 0) == 6


# -- the cluster feed (the fast twin of test_delta's slow test) -------------


def test_cluster_feed_serves_a_fresh_read_at_default_flags(tmp_path, flags):
    """One storaged, a handful of vertices: a write through the graphd's
    own store rides the dirty-key log (census-covered) into the pinned
    snapshot without a re-export, a delete is a tombstone, and the
    write's acknowledgement is timed."""
    from nebula_tpu.cluster.launcher import LocalCluster

    rt = TpuRuntime(make_mesh())
    c = LocalCluster(n_meta=1, n_storage=1, n_graph=1,
                     data_dir=str(tmp_path), tpu_runtime=rt)
    try:
        cl = c.client()
        r = cl.execute("CREATE SPACE dd(partition_num=8, "
                       "replica_factor=1, vid_type=INT64)")
        assert r.error is None, r.error
        c.reconcile_storage()
        for q in ["USE dd", "CREATE TAG T()", "CREATE EDGE E(w int)",
                  "INSERT VERTEX T() VALUES 1:(), 2:(), 3:(), 4:()",
                  "INSERT EDGE E(w) VALUES 1->2:(1), 2->3:(2)"]:
            assert cl.execute(q).error is None, q

        def friends():
            r = cl.execute("GO FROM 1 OVER E YIELD dst(edge) AS d, E.w AS w")
            assert r.error is None, r.error
            return sorted(map(tuple, r.data.rows))
        assert friends() == [(2, 1)]
        dev = rt.snapshots.get("dd")
        assert dev is not None and dev.delta is not None, \
            "a cluster pin at default flags did not arm the delta plane"
        assert dev.delta.host.dcap == 1024
        s0 = stats().snapshot()
        assert cl.execute("INSERT EDGE E(w) VALUES 1->3:(3), 1->4:(4)").error is None
        assert friends() == [(2, 1), (3, 3), (4, 4)]
        assert cl.execute("INSERT EDGE E(w) VALUES 1->2:(7)").error is None     # overwrite
        assert friends() == [(2, 7), (3, 3), (4, 4)]
        assert cl.execute("DELETE EDGE E 1->3@0").error is None                # tombstone
        assert friends() == [(2, 7), (4, 4)]
        s1 = stats().snapshot()
        assert rt.snapshots["dd"] is dev, "a cluster write should ride the delta, not re-export"
        assert s1.get("tpu_pins", 0) == s0.get("tpu_pins", 0)
        assert s1["tpu_repin_avoided"] - s0.get("tpu_repin_avoided", 0) == 3
        assert s1["write_ack_s.count"] - s0.get("write_ack_s.count", 0) == 3
        assert 0 < s1["write_ack_s.sum"] - s0.get("write_ack_s.sum", 0) < 30
        assert s1["tpu_delta_apply_s.count"] - s0.get("tpu_delta_apply_s.count", 0) == 3
    finally:
        c.stop()
