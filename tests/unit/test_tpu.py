"""TPU device-plane tests: every kernel against its host oracle on the
8-device virtual CPU mesh (conftest sets XLA_FLAGS / JAX_PLATFORMS), per
SURVEY §4's CPU-oracle strategy."""
import random

import numpy as np
import pytest

from nebula_tpu.core.value import NULL
from nebula_tpu.exec.engine import QueryEngine
from nebula_tpu.graphstore.csr import build_snapshot, expand_frontier_host
from nebula_tpu.graphstore.schema import PropDef, PropType
from nebula_tpu.graphstore.store import GraphStore

tpu = pytest.importorskip("nebula_tpu.tpu")
from nebula_tpu.tpu import TpuRuntime, make_mesh, pin_snapshot  # noqa: E402
from nebula_tpu.tpu.exprjit import compilable, compile_predicate  # noqa: E402

P = 8


def random_store(seed=0, n=120, avg_deg=5, spacename="g",
                 extra_edge_type=False):
    rng = random.Random(seed)
    st = GraphStore()
    st.create_space(spacename, partition_num=P, vid_type="INT64")
    st.catalog.create_tag(spacename, "person", [
        PropDef("age", PropType.INT64), PropDef("name", PropType.STRING)])
    st.catalog.create_edge(spacename, "knows", [
        PropDef("w", PropType.INT64), PropDef("f", PropType.DOUBLE),
        PropDef("tag", PropType.STRING)])
    if extra_edge_type:
        st.catalog.create_edge(spacename, "likes", [
            PropDef("w", PropType.INT64)])
    names = ["ann", "bob", "cid", "dee"]
    for v in range(n):
        st.insert_vertex(spacename, v, "person",
                         {"age": rng.randint(0, 80), "name": rng.choice(names)})
    for v in range(n):
        for _ in range(rng.randint(0, avg_deg * 2)):
            d = rng.randrange(n)
            props = {"w": rng.randint(-5, 100) if rng.random() > .1 else NULL,
                     "f": rng.uniform(0, 1), "tag": rng.choice(names)}
            st.insert_edge(spacename, v, "knows", d, rng.randint(0, 2), props)
        if extra_edge_type and rng.random() > .5:
            st.insert_edge(spacename, v, "likes", rng.randrange(n), 0,
                           {"w": rng.randint(0, 10)})
    return st


def norm_edge(e):
    """Same normalization as the src()/dst() builtins: reversed edges
    (etype<0) report their stored orientation."""
    if e.etype >= 0:
        return repr([e.src, e.name, e.ranking, e.dst])
    return repr([e.dst, e.name, e.ranking, e.src])


def host_go(st, space, vids, etypes, direction, steps, where_text=None):
    """Host-truth GO result as a sorted list of (src, etype, rank, dst)."""
    eng = QueryEngine(st)
    s = eng.new_session()
    eng.execute(s, f"USE {space}")
    w = f" WHERE {where_text}" if where_text else ""
    q = (f"GO {steps} STEPS FROM {', '.join(map(str, vids))} "
         f"OVER {', '.join(etypes)}"
         + (" REVERSELY" if direction == "in" else
            " BIDIRECT" if direction == "both" else "")
         + w + " YIELD src(edge), type(edge), rank(edge), dst(edge)")
    rs = eng.execute(s, q)
    assert rs.error is None, f"{q} -> {rs.error}"
    return sorted(map(repr, rs.data.rows))


@pytest.fixture(scope="module")
def rt():
    return TpuRuntime(make_mesh(P))


def test_pin_and_hbm(rt):
    st = random_store(1)
    dev = rt.pin(st, "g")
    assert dev.num_parts == P
    assert dev.hbm_bytes() > 0
    # same epoch → cached object
    assert rt.pin(st, "g") is dev
    # a write bumps the epoch: at default flags the armed delta plane
    # takes it (the snapshot stays, its served epoch advances) ...
    assert dev.delta is not None
    st.insert_edge("g", 0, "knows", 1, 9, {"w": 1, "f": .5, "tag": "x"})
    dev2 = rt.pin(st, "g")
    assert dev2 is dev and rt._served_epoch(dev) == st.space("g").epoch
    assert rt._served_epoch(dev) != dev.epoch
    # ... and with the plane off (the flag's explicit 0) it re-pins
    from nebula_tpu.utils.config import get_config
    cfg = get_config()
    cfg.set_dynamic("tpu_delta_max_edges", 0)
    try:
        rt0 = TpuRuntime(make_mesh(P))
        dev = rt0.pin(st, "g")
        assert dev.delta is None
        st.insert_edge("g", 0, "knows", 2, 9, {"w": 1, "f": .5, "tag": "x"})
        dev2 = rt0.pin(st, "g")
        assert dev2 is not dev and dev2.epoch != dev.epoch
    finally:
        with cfg.lock:
            cfg.dynamic_layer.pop("tpu_delta_max_edges", None)


@pytest.mark.parametrize("steps", [1, 2, 3])
@pytest.mark.parametrize("direction", ["out", "in", "both"])
def test_traverse_matches_host(rt, steps, direction):
    st = random_store(2)
    sources = [3, 17, 44]
    rows, stats = rt.traverse(st, "g", sources, ["knows"], direction, steps)
    got = sorted(norm_edge(e) for (_, e, _) in rows)
    want = host_go(st, "g", sources, ["knows"], direction, steps)
    assert got == want
    assert stats.edges_traversed() >= len(rows)


def test_traverse_multi_etype(rt):
    st = random_store(3, extra_edge_type=True)
    rows, _ = rt.traverse(st, "g", [1, 2, 3], ["knows", "likes"], "out", 2)
    got = sorted(norm_edge(e) for (_, e, _) in rows)
    want = host_go(st, "g", [1, 2, 3], ["knows", "likes"], "out", 2)
    assert got == want


def test_frontier_oracle(rt):
    """One-hop device frontier == expand_frontier_host on the raw CSR."""
    st = random_store(4)
    snap = build_snapshot(st, "g")
    blk = snap.block("knows", "out")
    sd = st.space("g")
    dense = [sd.dense_id(v) for v in [5, 9]]
    want = expand_frontier_host(snap, blk, np.asarray(dense, np.int32))
    # run a 2-step traverse and recover its intermediate frontier from the
    # final hop's sources
    rows, _ = rt.traverse(st, "g", [5, 9], ["knows"], "out", 2)
    springs = sorted({sd.dense_id(e.src) for (_, e, _) in rows})
    # sources of hop 2 ⊆ hop-1 frontier; vertices with no out-edges appear
    # in `want` but not as hop-2 sources
    assert set(springs) <= set(int(x) for x in want)


@pytest.mark.parametrize("where", [
    "knows.w > 30",
    "knows.w >= 10 AND knows.w < 60",
    "knows.f < 0.5 OR knows.w == 7",
    "knows.tag == \"ann\"",
    "knows.tag != \"bob\" AND knows.w % 2 == 0",
    "knows.w IS NOT NULL AND knows.w * 2 + 1 > 21",
    "knows.w IN [1, 2, 3, 40, 41, 42, 43, 44]",
    "rank(edge) == 1",
    "id($$) == 9",
    "id($$) != 9 AND knows.w > 20",
    "id($$) IN [5, 9, 14, 999999]",
    "id($$) NOT IN [5, 9]",
    "id($^) == 3",
    "NOT (knows.w > 10)",
    "knows.w / 3 > 5",
    "(knows.w & 1) == 0",
    "(knows.w ^ 3) > 40",
    "(knows.w | 8) < 60",
    # both halves of an int64 and of a double rebuilt per slot (PR 35)
    "knows.w < 0 OR knows.f >= 0.75",
    "knows.f > 0.25 AND knows.f < 0.5 AND knows.w != -3",
])
def test_predicate_parity(rt, where):
    st = random_store(5)
    from nebula_tpu.query.parser import parse
    stmt = parse(f"GO 2 STEPS FROM 3, 17 OVER knows WHERE {where} "
                 f"YIELD src(edge), type(edge), rank(edge), dst(edge)")
    cond = stmt.where.filter if stmt.where else None
    assert cond is not None
    assert compilable(cond, ["knows"]), where
    rows, _ = rt.traverse(st, "g", [3, 17], ["knows"], "out", 2,
                          edge_filter=cond)
    got = sorted(norm_edge(e) for (_, e, _) in rows)
    want = host_go(st, "g", [3, 17], ["knows"], "out", 2, where)
    assert got == want, where


def test_not_compilable():
    from nebula_tpu.query.parser import parse
    for w in ["knows.tag CONTAINS \"a\"",
              "knows.tag =~ \"a.*\"",
              "id($$) + 1 == 3",
              "id($$) == id($^)"]:
        stmt = parse(f"GO FROM 1 OVER knows WHERE {w} YIELD dst(edge)")
        assert not compilable(stmt.where.filter, ["knows"]), w


def test_string_ordering_falls_back(rt):
    """String ordering passes the structural gate but fails typed compile;
    the executor must fall back to the host path with identical rows."""
    st = random_store(5)
    eng = QueryEngine(st, tpu_runtime=rt)
    s = eng.new_session()
    eng.execute(s, "USE g")
    q = ('GO 2 STEPS FROM 3, 17 OVER knows WHERE knows.tag < "m" '
         'YIELD src(edge), rank(edge), dst(edge)')
    rs = eng.execute(s, q)
    assert rs.error is None, rs.error
    want = QueryEngine(st)
    s2 = want.new_session()
    want.execute(s2, "USE g")
    rs2 = want.execute(s2, q)
    assert sorted(map(repr, rs.data.rows)) == sorted(map(repr, rs2.data.rows))


def test_bucket_escalation(rt):
    """Tiny initial buckets must converge via doubling, same answer."""
    st = random_store(6, n=200, avg_deg=8)
    small = TpuRuntime(make_mesh(P))
    small.init_eb = 4
    rows, stats = small.traverse(st, "g", [1, 2, 3, 4], ["knows"], "out", 3)
    got = sorted(norm_edge(e) for (_, e, _) in rows)
    want = host_go(st, "g", [1, 2, 3, 4], ["knows"], "out", 3)
    assert got == want
    assert stats.retries > 0


@pytest.mark.parametrize("direction", ["", " REVERSELY", " BIDIRECT"])
def test_find_shortest_path_device_parity(rt, direction):
    """Device BFS + host reconstruction must yield the exact path rows of
    the host multi-parent BFS, for every direction."""
    st = random_store(11, n=80, avg_deg=4)
    eng_tpu = QueryEngine(st, tpu_runtime=rt)
    eng_cpu = QueryEngine(st)
    pairs = [(1, 40), (3, 9), (17, 2), (5, 77)]
    for (a, b) in pairs:
        q = (f"FIND SHORTEST PATH FROM {a} TO {b} OVER knows{direction} "
             f"UPTO 5 STEPS YIELD path AS p")
        got = {}
        for eng in (eng_tpu, eng_cpu):
            s = eng.new_session()
            eng.execute(s, "USE g")
            rs = eng.execute(s, q)
            assert rs.error is None, (q, rs.error)
            got[id(eng)] = sorted(map(repr, rs.data.rows))
        assert got[id(eng_tpu)] == got[id(eng_cpu)], q
    # the device plane actually served — no silent host fallback
    assert getattr(eng_tpu.qctx, "last_tpu_fallback", None) is None


def test_find_shortest_multi_src_dst_device_parity(rt):
    st = random_store(12, n=60, avg_deg=4)
    q = ("FIND SHORTEST PATH FROM 1, 2, 3 TO 30, 31 OVER knows "
         "UPTO 4 STEPS YIELD path AS p")
    res = {}
    for tpu_on in (True, False):
        eng = QueryEngine(st, tpu_runtime=rt if tpu_on else None)
        s = eng.new_session()
        eng.execute(s, "USE g")
        rs = eng.execute(s, q)
        assert rs.error is None, rs.error
        res[tpu_on] = sorted(map(repr, rs.data.rows))
    assert res[True] == res[False]


def test_engine_fusion_end_to_end(rt):
    """Same query, optimizer TPU rule ON vs OFF → identical row multisets,
    and the fused plan actually contains TpuTraverse."""
    st = random_store(7)
    eng_cpu = QueryEngine(st)
    eng_tpu = QueryEngine(st, tpu_runtime=rt)
    q = ("GO 3 STEPS FROM 3, 17, 44 OVER knows WHERE knows.w > 10 "
         "YIELD src(edge) AS s, dst(edge) AS d, knows.w AS w")
    for eng in (eng_cpu, eng_tpu):
        s = eng.new_session()
        eng.execute(s, "USE g")
        rs = eng.execute(s, q)
        assert rs.error is None, rs.error
        eng._last = sorted(map(repr, rs.data.rows))
    assert eng_cpu._last == eng_tpu._last

    s = eng_tpu.new_session()
    eng_tpu.execute(s, "USE g")
    rs = eng_tpu.execute(s, "EXPLAIN " + q)
    assert "TpuTraverse" in rs.data.rows[0][0]
    s2 = eng_cpu.new_session()
    eng_cpu.execute(s2, "USE g")
    rs = eng_cpu.execute(s2, "EXPLAIN " + q)
    assert "TpuTraverse" not in rs.data.rows[0][0]


def test_mton_and_piped_go_parity(rt):
    """m-TO-n GO and $- piped GO may fuse sub-chains (single-use 1-step
    heads) but must keep exact row parity with the host path."""
    st = random_store(8)
    qs = ["GO 1 TO 3 STEPS FROM 3 OVER knows YIELD src(edge), dst(edge)",
          "GO FROM 3 OVER knows YIELD dst(edge) AS d "
          "| GO FROM $-.d OVER knows YIELD $-.d, dst(edge)"]
    for q in qs:
        out = []
        for tpu_rt in (None, rt):
            eng = QueryEngine(st, tpu_runtime=tpu_rt)
            s = eng.new_session()
            eng.execute(s, "USE g")
            rs = eng.execute(s, q)
            assert rs.error is None, f"{q} -> {rs.error}"
            out.append(sorted(map(repr, rs.data.rows)))
        assert out[0] == out[1], q


def test_write_invalidates_snapshot(rt):
    st = random_store(9)
    rows1, _ = rt.traverse(st, "g", [3], ["knows"], "out", 1)
    st.insert_edge("g", 3, "knows", 99, 7, {"w": 50, "f": .1, "tag": "zz"})
    rows2, _ = rt.traverse(st, "g", [3], ["knows"], "out", 1)
    assert len(rows2) == len(rows1) + 1


def test_single_chip_local_mode():
    """Mesh of 1 device serves an 8-partition space via the vmap driver —
    the real-TPU bench configuration."""
    st = random_store(11)
    rt1 = TpuRuntime(make_mesh(1))
    assert rt1.local_mode
    rows, stats = rt1.traverse(st, "g", [3, 17, 44], ["knows"], "out", 3)
    got = sorted(norm_edge(e) for (_, e, _) in rows)
    want = host_go(st, "g", [3, 17, 44], ["knows"], "out", 3)
    assert got == want


def test_temporal_and_overflow_predicates_fall_back(rt):
    """Code-review regressions: DATETIME-vs-int compares and out-of-int64
    literals must produce host-identical results (via fallback)."""
    st = GraphStore()
    st.create_space("t", partition_num=P, vid_type="INT64")
    st.catalog.create_edge("t", "e", [PropDef("ts", PropType.DATETIME),
                                      PropDef("w", PropType.INT64)])
    from nebula_tpu.core.value import DateTime
    st.insert_edge("t", 1, "e", 2, 0, {"ts": DateTime(2020, 5, 1, 12), "w": 3})
    st.insert_edge("t", 2, "e", 3, 0, {"ts": DateTime(2021, 6, 2, 13), "w": 4})
    for q in [
        # datetime-vs-datetime compares refuse device compilation (the
        # encodings are order-isomorphic but the mask compiler keeps
        # temporal kinds distinct); datetime-vs-INT is now rejected
        # upstream by the validator's type deduction
        'GO 2 STEPS FROM 1 OVER e WHERE e.ts > datetime("2020-12-01T00:00:00") '
        "YIELD src(edge), dst(edge)",
        "GO 2 STEPS FROM 1 OVER e WHERE e.w < 99999999999999999999999 "
        "YIELD src(edge), dst(edge)",
        "GO 2 STEPS FROM 1 OVER e WHERE e.w IN [\"x\", 3] "
        "YIELD src(edge), dst(edge)",
    ]:
        out = []
        for tr in (None, rt):
            eng = QueryEngine(st, tpu_runtime=tr)
            s = eng.new_session()
            eng.execute(s, "USE t")
            r = eng.execute(s, q)
            assert r.error is None, (q, r.error)
            out.append(sorted(map(repr, r.data.rows)))
        assert out[0] == out[1], q


def test_pre_epoch_datetime_roundtrip():
    """Encoding must be monotonic and lossless across the 1970 epoch."""
    from nebula_tpu.core.value import DateTime
    from nebula_tpu.graphstore.csr import (StringPool, decode_prop,
                                           encode_prop)
    pool = StringPool()
    vals = [DateTime(1944, 6, 6, 6, 30, 0, 1),
            DateTime(1969, 12, 31, 23, 59, 59, 500000),
            DateTime(1970, 1, 1, 0, 0, 0, 0),
            DateTime(1970, 1, 1, 0, 0, 0, 250000),
            DateTime(2024, 2, 29, 23, 59, 59, 999999)]
    enc = [encode_prop(PropType.DATETIME, v, pool) for v in vals]
    assert enc == sorted(enc)
    for v, e in zip(vals, enc):
        assert decode_prop(PropType.DATETIME, e, pool) == v


def test_yield_fusion_columnar_parity(rt):
    """Project(go_row) absorbed into TpuTraverse: all yieldable column
    shapes (src/dst/rank/type/typeid, edge props incl. strings, literal,
    reverse direction) match the host path row-for-row."""
    st = random_store(13)
    qs = [
        "GO 2 STEPS FROM 3, 17 OVER knows "
        "YIELD src(edge) AS s, dst(edge) AS d, rank(edge) AS r, "
        "type(edge) AS t, knows.w AS w, knows.tag AS g, 7 AS c",
        "GO 2 STEPS FROM 3, 17 OVER knows REVERSELY "
        "YIELD src(edge), dst(edge), knows.tag",
        "GO 3 STEPS FROM 3 OVER knows WHERE knows.w > 20 "
        "YIELD dst(edge), knows.w, knows.f",
    ]
    for q in qs:
        out = []
        for tpu_rt in (None, rt):
            eng = QueryEngine(st, tpu_runtime=tpu_rt)
            s = eng.new_session()
            eng.execute(s, "USE g")
            rs = eng.execute(s, q)
            assert rs.error is None, f"{q} -> {rs.error}"
            out.append(sorted(map(repr, rs.data.rows)))
        assert out[0] == out[1], q

    # the fused plan carries the yields (no separate Project above)
    eng = QueryEngine(st, tpu_runtime=rt)
    s = eng.new_session()
    eng.execute(s, "USE g")
    rs = eng.execute(s, "EXPLAIN " + qs[0])
    desc = rs.data.rows[0][0]
    assert "TpuTraverse" in desc and "yields" in desc
    assert desc.strip().startswith("TpuTraverse"), desc


def test_non_yieldable_keeps_project(rt):
    """$$-prop yields can't be columnar: Project survives, the chain
    below still fuses, and parity holds."""
    st = random_store(14)
    q = ("GO 2 STEPS FROM 3 OVER knows "
         "YIELD dst(edge) AS d, $$.person.age AS a")
    out = []
    for tpu_rt in (None, rt):
        eng = QueryEngine(st, tpu_runtime=tpu_rt)
        s = eng.new_session()
        eng.execute(s, "USE g")
        rs = eng.execute(s, q)
        assert rs.error is None, rs.error
        out.append(sorted(map(repr, rs.data.rows)))
    assert out[0] == out[1]
    eng = QueryEngine(st, tpu_runtime=rt)
    s = eng.new_session()
    eng.execute(s, "USE g")
    rs = eng.execute(s, "EXPLAIN " + q)
    desc = rs.data.rows[0][0]
    assert "Project" in desc and "TpuTraverse" in desc


# ---------------------------------------------------------------------------
# MATCH device plane (Traverse via layered hop frames)
# ---------------------------------------------------------------------------


MATCH_QS = [
    # fixed 1-hop with edge alias + props
    "MATCH (a:person)-[e:knows]->(b) WHERE id(a) IN [3, 17, 44] "
    "RETURN id(a), e.w, rank(e), id(b)",
    # reverse and undirected
    "MATCH (a:person)<-[e:knows]-(b) WHERE id(a) == 7 RETURN id(b), e.w",
    "MATCH (a:person)-[e:knows]-(b) WHERE id(a) == 7 RETURN id(b), rank(e)",
    # variable-length: *1..3, *0..2, exact *2
    "MATCH (a:person)-[e:knows*1..3]->(b) WHERE id(a) == 5 "
    "RETURN id(b), size(e)",
    "MATCH (a:person)-[e:knows*0..2]->(b) WHERE id(a) IN [3, 9] "
    "RETURN id(a), id(b)",
    "MATCH (a:person)-[e:knows*2]->(b) WHERE id(a) IN [1, 2] "
    "RETURN id(b)",
    # inline edge-prop predicate (device-compiled per-hop mask)
    "MATCH (a:person)-[e:knows*1..2 {tag: 'ann'}]->(b) WHERE id(a) IN "
    "[3, 17] RETURN id(b), size(e)",
    # longer pattern: two fixed hops + node filter
    "MATCH (a:person)-[e1:knows]->(m)-[e2:knows]->(b:person) "
    "WHERE id(a) == 5 AND b.person.age > 30 RETURN id(m), id(b)",
]


@pytest.mark.parametrize("q", MATCH_QS)
def test_match_traverse_device_parity(rt, q):
    """MATCH Traverse runs on the device plane (layered hop frames +
    host trail assembly) with identical result rows to the host DFS."""
    st = random_store(21)
    out = []
    for tpu_rt in (None, rt):
        eng = QueryEngine(st, tpu_runtime=tpu_rt)
        s = eng.new_session()
        eng.execute(s, "USE g")
        rs = eng.execute(s, q)
        assert rs.error is None, f"{q} -> {rs.error}"
        out.append(sorted(map(repr, rs.data.rows)))
    assert out[0] == out[1], q


def test_match_device_engages(rt):
    """The device plane actually runs (stats recorded), and the flag
    turns it off."""
    from nebula_tpu.utils.config import get_config
    st = random_store(22)
    eng = QueryEngine(st, tpu_runtime=rt)
    s = eng.new_session()
    eng.execute(s, "USE g")
    q = "MATCH (a:person)-[e:knows*1..3]->(b) WHERE id(a) == 5 RETURN id(b)"
    rs = eng.execute(s, q)
    assert rs.error is None
    st_stats = eng.qctx.last_tpu_stats
    assert st_stats is not None and st_stats.steps == 3
    assert st_stats.edges_traversed() > 0
    want = sorted(map(repr, rs.data.rows))

    get_config().set_dynamic("tpu_match_device", False)
    try:
        eng2 = QueryEngine(st, tpu_runtime=rt)
        s2 = eng2.new_session()
        eng2.execute(s2, "USE g")
        rs2 = eng2.execute(s2, q)
        assert eng2.qctx.last_tpu_stats is None
        assert sorted(map(repr, rs2.data.rows)) == want
    finally:
        get_config().set_dynamic("tpu_match_device", True)


def test_match_multi_etype_prop_pred_hybrid(rt):
    """Multi-etype pattern with an inline prop predicate can't compile a
    device mask — frames come back unfiltered and edge_ok re-checks on
    host during assembly.  Rows must still match the pure host path."""
    st = random_store(23, extra_edge_type=True)
    q = ("MATCH (a:person)-[e:knows|likes*1..2 {w: 1}]->(b) "
         "WHERE id(a) IN [1, 2, 3, 4, 5] RETURN id(b), size(e)")
    out = []
    for tpu_rt in (None, rt):
        eng = QueryEngine(st, tpu_runtime=tpu_rt)
        s = eng.new_session()
        eng.execute(s, "USE g")
        rs = eng.execute(s, q)
        assert rs.error is None, rs.error
        out.append(sorted(map(repr, rs.data.rows)))
    assert out[0] == out[1]


def test_serve_while_repin_stress(rt):
    """Systematic epoch-fencing check (SURVEY §5 race detection): query
    threads traverse while a writer mutates the store (each write bumps
    the epoch and forces a re-pin).  Every result must be internally
    consistent — a traversal may serve the pre- or post-write snapshot,
    but never a torn mix, and the final settled result must equal the
    host oracle.

    The jaxlib CPU race this used to flake on (CHANGES.md PR 6 note:
    concurrent jitted dispatches deadlocking against a device_put,
    2/20 runs) is closed by TpuRuntime's dispatch-vs-repin read-write
    gate (ISSUE 9): dispatches share, a re-pin drains and excludes
    them.  ALARM-GUARDED: the workers are daemon threads joined with a
    timeout, so a regression fails in seconds with the live thread
    stacks instead of wedging the whole 870 s tier-1 budget."""
    import threading
    import time as _time

    st = random_store(31)
    errs = []
    baseline = len(rt.traverse(st, "g", [3], ["knows"], "out", 2)[0])

    def writer():
        for i in range(12):
            st.insert_edge("g", 3, "knows", 200 + i, 0,
                           {"w": 5, "f": .5, "tag": "zz"})

    def reader():
        try:
            prev = baseline
            for _ in range(10):
                rows, _ = rt.traverse(st, "g", [3], ["knows"], "out", 2)
                # monotone: writer only ADDS edges reachable from the
                # seed, so a consistent snapshot can never shrink
                assert len(rows) >= prev, (len(rows), prev)
                prev = len(rows)
        except Exception as ex:  # noqa: BLE001
            errs.append(ex)

    ts = [threading.Thread(target=writer, daemon=True)] + \
        [threading.Thread(target=reader, daemon=True) for _ in range(2)]
    for t in ts:
        t.start()
    deadline = _time.monotonic() + 120.0
    stuck = []
    for t in ts:
        t.join(timeout=max(deadline - _time.monotonic(), 0.1))
        if t.is_alive():
            stuck.append(t.name)
    if stuck:
        from nebula_tpu.utils.workload import _thread_stacks
        dump = "\n".join(f"--- {k}\n" + "\n".join(v[-4:])
                         for k, v in _thread_stacks().items())
        pytest.fail(f"serve-while-repin deadlock: {stuck} still alive "
                    f"after 120s\n{dump}")
    assert not errs, errs
    # settled: device result equals host oracle exactly
    rows, _ = rt.traverse(st, "g", [3], ["knows"], "out", 2)
    got = sorted(norm_edge(e) for (_, e, _) in rows)
    assert got == host_go(st, "g", [3], ["knows"], "out", 2)


def test_dispatch_gate_semantics(rt):
    """The dispatch-vs-repin gate (ISSUE 9): readers share; a writer
    excludes readers AND blocks new ones while waiting (writer
    preference, so a dispatch stream cannot starve an epoch bump)."""
    import threading
    import time as _time

    from nebula_tpu.tpu.runtime import _DispatchGate
    g = _DispatchGate()
    log = []
    r1_in = threading.Event()
    release_r1 = threading.Event()

    def reader1():
        g.acquire_read()
        log.append("r1+")
        r1_in.set()
        release_r1.wait(5)
        log.append("r1-")
        g.release_read()

    def writer():
        r1_in.wait(5)
        log.append("w?")
        g.acquire_write()          # blocks until r1 releases
        log.append("w+")
        g.release_write()

    t1 = threading.Thread(target=reader1, daemon=True)
    tw = threading.Thread(target=writer, daemon=True)
    t1.start()
    tw.start()
    r1_in.wait(5)
    # wait until the writer is REGISTERED as waiting (polling the
    # gate's own counter — a blind sleep races thread scheduling on a
    # loaded test VM)
    t0 = _time.monotonic()
    while g._writers_waiting == 0 and _time.monotonic() - t0 < 5.0:
        _time.sleep(0.005)
    assert g._writers_waiting == 1, "writer never queued"
    got2 = []

    def reader2():
        g.acquire_read()           # writer waiting → must block
        got2.append(True)
        g.release_read()

    t2 = threading.Thread(target=reader2, daemon=True)
    t2.start()
    _time.sleep(0.1)
    assert not got2, "reader overtook a waiting writer"
    release_r1.set()
    tw.join(5)
    t2.join(5)
    assert log[-1] == "w+" or "w+" in log
    assert got2 == [True]
    t1.join(5)


def test_failpoint_delayed_dispatch_stall_dump(rt):
    """Acceptance shape (ISSUE 9): a failpoint-delayed device dispatch
    produces a stall capture — thread stacks + the in-flight dispatch
    table + the kernel-ledger tail — while the query's rows stay
    byte-identical to an uninstrumented run (the watchdog observes,
    never touches)."""
    import threading
    import time as _time

    from nebula_tpu.utils.config import get_config
    from nebula_tpu.utils.failpoints import fail
    from nebula_tpu.utils.workload import stall_watchdog

    st = random_store(62)
    want, _ = rt.traverse(st, "g", [3], ["knows"], "out", 2)
    want = sorted(norm_edge(e) for (_, e, _) in want)
    stall_watchdog().clear()
    get_config().set_dynamic("stall_threshold_secs", 0.05)
    fail.arm("tpu:dispatch_gate", "1*delay(0.4)")
    try:
        box = {}

        def run():
            box["rows"], _ = rt.traverse(st, "g", [3], ["knows"],
                                         "out", 2)

        t = threading.Thread(target=run, daemon=True)
        t.start()
        t0 = _time.monotonic()
        found = []
        while _time.monotonic() - t0 < 5.0 and not found:
            # poll the RING, not scan_once()'s return — the engine's
            # background watchdog may win the capture race
            stall_watchdog().scan_once()
            found = [e for e in stall_watchdog().list()
                     if e["kind"] == "dispatch"]
            _time.sleep(0.02)
        t.join(30)
        assert len(found) == 1, "delayed dispatch was never captured"
        summ = found[0]
        full = stall_watchdog().get(summ["id"])
        assert full["stacks"], "no thread stacks in the stall dump"
        assert isinstance(full["kernels"], list)
        assert full["subject"]["state"] == "queued"
        got = sorted(norm_edge(e) for (_, e, _) in box["rows"])
        assert got == want, "stall capture perturbed the result rows"
    finally:
        fail.reset()
        stall_watchdog().clear()
        get_config().dynamic_layer.pop("stall_threshold_secs", None)


def test_dispatch_queue_accounting(rt):
    """Every device dispatch reports its wait-vs-run decomposition:
    tpu_dispatch_queue_us{kernel} moves, TraverseStats carries queue_s,
    the queue-depth gauge settles back to zero, and the dispatch table
    is empty once the statement finishes (ISSUE 9)."""
    from nebula_tpu.utils.stats import stats as _stats
    from nebula_tpu.utils.workload import dispatch_table

    st = random_store(61)
    before = _stats().snapshot().get(
        "tpu_dispatch_queue_us{kernel=traverse}.count", 0)
    rows, tstats = rt.traverse(st, "g", [3], ["knows"], "out", 2)
    assert rows
    assert tstats.queue_s >= 0.0
    snap = _stats().snapshot()
    assert snap.get("tpu_dispatch_queue_us{kernel=traverse}.count",
                    0) > before
    assert snap.get("tpu_dispatch_queue_depth", 0) == 0
    assert len(dispatch_table()) == 0


SUBGRAPH_QS = [
    'GET SUBGRAPH 2 STEPS FROM 3 YIELD VERTICES AS v, EDGES AS e',
    'GET SUBGRAPH 3 STEPS FROM 3, 17 BOTH knows YIELD VERTICES AS v, '
    'EDGES AS e',
    'GET SUBGRAPH 2 STEPS FROM 5 OUT knows YIELD VERTICES AS v, EDGES AS e',
    'GET SUBGRAPH 2 STEPS FROM 5 IN knows YIELD VERTICES AS v, EDGES AS e',
    'GET SUBGRAPH WITH PROP 2 STEPS FROM 3 OUT knows YIELD VERTICES AS v, '
    'EDGES AS e',
    'GET SUBGRAPH 2 STEPS FROM 3 OUT knows WHERE knows.w > 30 '
    'YIELD VERTICES AS v, EDGES AS e',
    'GET SUBGRAPH 1 STEPS FROM 44 YIELD EDGES AS e',
    # non-compilable predicate: frames come back unfiltered and the
    # shared assembler's edge_ok host re-check prunes during replay
    'GET SUBGRAPH 2 STEPS FROM 3 OUT knows WHERE knows.tag CONTAINS "a" '
    'YIELD VERTICES AS v, EDGES AS e',
]


@pytest.mark.parametrize("q", SUBGRAPH_QS)
def test_subgraph_device_parity(rt, q):
    """GET SUBGRAPH rides the device hop-frame plane with rows
    byte-identical (including intra-cell list order) to the host BFS."""
    st = random_store(41)
    out = []
    for tpu_rt in (None, rt):
        eng = QueryEngine(st, tpu_runtime=tpu_rt)
        s = eng.new_session()
        eng.execute(s, "USE g")
        rs = eng.execute(s, q)
        assert rs.error is None, f"{q} -> {rs.error}"
        out.append([[repr(c) for c in row] for row in rs.data.rows])
    assert out[0] == out[1], q


def test_subgraph_device_engages(rt):
    st = random_store(42)
    eng = QueryEngine(st, tpu_runtime=rt)
    s = eng.new_session()
    eng.execute(s, "USE g")
    rs = eng.execute(s, 'GET SUBGRAPH 2 STEPS FROM 3 OUT knows '
                        'YIELD VERTICES AS v, EDGES AS e')
    assert rs.error is None
    assert eng.qctx.last_tpu_stats is not None
    assert eng.qctx.last_tpu_stats.edges_traversed() > 0


PATH_QS = [
    'FIND ALL PATH FROM 3 TO 44 OVER knows UPTO 3 STEPS YIELD path AS p',
    'FIND ALL PATH FROM 3, 17 TO 44, 5 OVER knows UPTO 4 STEPS '
    'YIELD path AS p',
    'FIND NOLOOP PATH FROM 3 TO 44 OVER knows UPTO 4 STEPS YIELD path AS p',
    'FIND ALL PATH WITH PROP FROM 3 TO 44 OVER knows UPTO 3 STEPS '
    'YIELD path AS p',
    'FIND ALL PATH FROM 3 TO 3 OVER knows UPTO 3 STEPS YIELD path AS p',
]


@pytest.mark.parametrize("q", PATH_QS)
def test_find_path_device_parity(rt, q):
    """FIND ALL/NOLOOP PATH rides the device hop-frame plane with rows
    identical to the host DFS."""
    st = random_store(51)
    out = []
    for tpu_rt in (None, rt):
        eng = QueryEngine(st, tpu_runtime=tpu_rt)
        s = eng.new_session()
        eng.execute(s, "USE g")
        rs = eng.execute(s, q)
        assert rs.error is None, f"{q} -> {rs.error}"
        out.append([[repr(c) for c in row] for row in rs.data.rows])
    assert out[0] == out[1], q


def test_find_path_device_engages(rt):
    st = random_store(52)
    eng = QueryEngine(st, tpu_runtime=rt)
    s = eng.new_session()
    eng.execute(s, "USE g")
    rs = eng.execute(s, 'FIND ALL PATH FROM 3 TO 44 OVER knows '
                        'UPTO 3 STEPS YIELD path AS p')
    assert rs.error is None
    assert eng.qctx.last_tpu_stats is not None


SHORTEST_FILTER_QS = [
    'FIND SHORTEST PATH FROM 3 TO 44 OVER knows WHERE knows.w > 20 '
    'UPTO 5 STEPS YIELD path AS p',
    'FIND SHORTEST PATH FROM 3 TO 44, 17 OVER knows WHERE knows.w >= 10 '
    'UPTO 4 STEPS YIELD path AS p',
    # non-compilable predicate → CannotCompile → host fallback, same rows
    'FIND SHORTEST PATH FROM 3 TO 44 OVER knows '
    'WHERE knows.tag CONTAINS "a" UPTO 5 STEPS YIELD path AS p',
]


@pytest.mark.parametrize("q", SHORTEST_FILTER_QS)
def test_filtered_shortest_path_device_parity(rt, q):
    """FIND SHORTEST PATH WHERE <pred> runs the masked device BFS (or
    falls back for non-compilable predicates) with host-identical
    rows."""
    st = random_store(61)
    out = []
    for tpu_rt in (None, rt):
        eng = QueryEngine(st, tpu_runtime=tpu_rt)
        s = eng.new_session()
        eng.execute(s, "USE g")
        rs = eng.execute(s, q)
        assert rs.error is None, f"{q} -> {rs.error}"
        out.append([[repr(c) for c in row] for row in rs.data.rows])
    assert out[0] == out[1], q


def test_filtered_shortest_path_multi_etype_falls_back(rt):
    """A prop predicate over multiple edge types can't compile one mask
    (exprjit forbids it); filtered shortest path must fall back to the
    host with identical rows, not KeyError."""
    st = random_store(62, extra_edge_type=True)
    q = ('FIND SHORTEST PATH FROM 3 TO 44 OVER knows, likes '
         'WHERE knows.w > 1 UPTO 4 STEPS YIELD path AS p')
    out = []
    for tpu_rt in (None, rt):
        eng = QueryEngine(st, tpu_runtime=tpu_rt)
        s = eng.new_session()
        eng.execute(s, "USE g")
        rs = eng.execute(s, q)
        assert rs.error is None, f"{q} -> {rs.error}"
        out.append([[repr(c) for c in row] for row in rs.data.rows])
    assert out[0] == out[1]


def test_bfs_single_compile_at_static_bounds(rt):
    """BFS buckets derive from static bounds (frontier <= vmax, hop
    edges <= padded Emax) so even a 1-seed BFS over a larger graph
    converges with ZERO escalation retries — every rung of the
    recompile ladder is a fresh XLA compile."""
    st = random_store(71, n=600, avg_deg=8)
    eng = QueryEngine(st, tpu_runtime=rt)
    s = eng.new_session()
    eng.execute(s, "USE g")
    rs = eng.execute(s, 'FIND SHORTEST PATH FROM 3 TO 599 OVER knows '
                        'UPTO 6 STEPS YIELD path AS p')
    assert rs.error is None, rs.error
    stats = eng.qctx.last_tpu_stats
    assert stats is not None
    assert stats.retries == 0, f"BFS escalated {stats.retries}x"


@pytest.mark.parametrize("seed", [101, 202, 303, 404, 505])
def test_device_parity_fuzz(rt, seed):
    """Randomized cross-surface parity sweep: for each random graph, a
    battery of GO / MATCH / SUBGRAPH / PATH / shortest queries must
    produce byte-identical rows host vs device (the 'identical result
    rows' north-star criterion, exercised beyond the hand-picked
    cases)."""
    import random as _r
    rng = _r.Random(seed)
    st = random_store(seed, n=rng.randint(60, 200),
                      avg_deg=rng.randint(3, 9))
    a, b = rng.randint(0, 59), rng.randint(0, 59)
    w = rng.randint(5, 60)
    qs = [
        f'GO {rng.randint(1, 3)} STEPS FROM {a} OVER knows '
        f'YIELD dst(edge) AS d, knows.w AS w',
        f'GO 2 STEPS FROM {a}, {b} OVER knows WHERE knows.w > {w} '
        f'YIELD src(edge) AS s, dst(edge) AS d',
        f'MATCH (x:person)-[e:knows*1..{rng.randint(2, 3)}]->(y) '
        f'WHERE id(x) == {a} RETURN id(y), size(e)',
        f'GET SUBGRAPH {rng.randint(1, 2)} STEPS FROM {a} OUT knows '
        f'YIELD VERTICES AS v, EDGES AS e',
        f'FIND ALL PATH FROM {a} TO {b} OVER knows UPTO 3 STEPS '
        f'YIELD path AS p',
        f'FIND SHORTEST PATH FROM {a} TO {b} OVER knows '
        f'WHERE knows.w > {w // 2} UPTO 4 STEPS YIELD path AS p',
    ]
    for q in qs:
        out = []
        for tpu_rt in (None, rt):
            eng = QueryEngine(st, tpu_runtime=tpu_rt)
            s = eng.new_session()
            eng.execute(s, "USE g")
            rs = eng.execute(s, q)
            assert rs.error is None, f"[seed {seed}] {q} -> {rs.error}"
            out.append(sorted(
                [[repr(c) for c in row] for row in rs.data.rows]))
        assert out[0] == out[1], f"[seed {seed}] {q}"


def test_pack_unpack_exchange_roundtrip():
    """The bit-packed frontier exchange: pack → OR → unpack must equal
    the bool OR for arbitrary mark matrices (incl. non-multiple-of-32
    vmax, empty, and full rows)."""
    import numpy as np
    from nebula_tpu.tpu.hop import _pack_bits, _unpack_or

    rng = np.random.default_rng(3)
    for vmax in (1, 31, 32, 33, 100, 257):
        for density in (0.0, 0.03, 0.5, 1.0):
            m = rng.random((4, vmax)) < density
            packed = _pack_bits(jnp_asarray(m))
            got = np.asarray(_unpack_or(packed, vmax))
            want = m.any(axis=0)
            assert (got == want).all(), (vmax, density)


def jnp_asarray(x):
    import jax.numpy as jnp
    return jnp.asarray(x)


def test_direction_optimizing_bfs_parity_local():
    """Single-chip BFS (the bench path) switches bottom-up on dense
    levels; distances must equal the numpy level-synchronous BFS, and
    the FIND SHORTEST PATH rows must equal the host engine's."""
    from nebula_tpu.bench.datagen import host_bfs
    from nebula_tpu.graphstore.csr import build_snapshot

    st = random_store(21, n=400, avg_deg=6)
    rt1 = TpuRuntime(make_mesh(1))          # local mode: have_rev leg
    assert rt1.local_mode
    snap = build_snapshot(st, "g")
    sd = st.space("g")
    for srcs in ([1], [2, 3, 5], list(range(40))):
        dist, stats = rt1.bfs(st, "g", srcs, ["knows"], "out", 6)
        dense = [sd.dense_id(v) for v in srcs]
        want = host_bfs(snap, dense, 6, etype="knows")
        got = np.asarray(dist, np.int32)
        nv = want.shape[0]
        vv = np.arange(nv)
        assert np.array_equal(got[vv % 8, vv // 8], want), srcs
    # engine-level rows: local runtime vs host path
    eng_dev = QueryEngine(st, tpu_runtime=rt1)
    eng_cpu = QueryEngine(st)
    q = ("FIND SHORTEST PATH FROM 1 TO 250 OVER knows UPTO 6 STEPS "
         "YIELD path AS p")
    got = {}
    for eng in (eng_dev, eng_cpu):
        s = eng.new_session()
        eng.execute(s, "USE g")
        rs = eng.execute(s, q)
        assert rs.error is None, rs.error
        got[id(eng)] = sorted(map(repr, rs.data.rows))
    assert got[id(eng_dev)] == got[id(eng_cpu)]


def test_bottom_up_bfs_endpoint_predicate_parity():
    """A filtered shortest path on a graph dense enough to flip the
    direction-optimizing kernel bottom-up must still evaluate
    id($^)/id($$) on TRAVERSAL orientation (the bottom-up expansion is
    reversed — endpoints swap inside the kernel)."""
    from nebula_tpu.query.parser import parse
    st = random_store(23, n=200, avg_deg=8)
    rt1 = TpuRuntime(make_mesh(1))
    assert rt1.local_mode
    for w in ("id($$) != 7", "id($^) NOT IN [3, 9]"):
        stmt = parse(f"GO FROM 1 OVER knows WHERE {w} YIELD dst(edge)")
        cond = stmt.where.filter
        dist, _ = rt1.bfs(st, "g", [1, 2, 3, 4, 5, 6, 7, 8], ["knows"],
                          "out", 5, edge_filter=cond)
        # host oracle: level BFS honoring the same edge filter
        import numpy as np
        eng = QueryEngine(st)
        s = eng.new_session()
        eng.execute(s, "USE g")
        frontier = {1, 2, 3, 4, 5, 6, 7, 8}
        want = {v: 0 for v in frontier}
        for lvl in range(1, 6):
            nxt = set()
            for (sv, et, rank, dv, props, sgn) in st.get_neighbors(
                    "g", sorted(frontier), ["knows"], "out"):
                if w == "id($$) != 7" and dv == 7:
                    continue
                if w == "id($^) NOT IN [3, 9]" and sv in (3, 9):
                    continue
                if dv not in want:
                    nxt.add(dv)
            for v in nxt:
                want[v] = lvl
            frontier = nxt
            if not frontier:
                break
        got = np.asarray(dist, np.int32)
        sd = st.space("g")
        for vid in range(200):
            d = sd.dense_id(vid)
            if d < 0:
                continue
            exp = want.get(vid, -1)
            assert got[d % 8, d // 8] == exp, (w, vid, exp,
                                               int(got[d % 8, d // 8]))


def test_non_identity_vid_decode(rt):
    """Spaces whose vids are NOT the dense ids must still decode through
    the d2v gather — guards the identity fast path in assemble._d2v
    (sequential-int-vid spaces skip the gather; scattered vids may not).
    Covers both the GO materializer and the MATCH frame decode."""
    from nebula_tpu.tpu.assemble import _d2v
    rng = random.Random(5)
    st = GraphStore()
    st.create_space("nid", partition_num=P, vid_type="INT64")
    st.catalog.create_tag("nid", "person", [PropDef("age", PropType.INT64)])
    st.catalog.create_edge("nid", "knows", [PropDef("w", PropType.INT64)])
    vids = [v * 13 + 1001 for v in range(80)]
    rng.shuffle(vids)
    for v in vids:
        st.insert_vertex("nid", v, "person", {"age": v % 90})
    for v in vids:
        for _ in range(rng.randint(0, 6)):
            st.insert_edge("nid", v, "knows", rng.choice(vids),
                           rng.randint(0, 2), {"w": rng.randint(0, 99)})
    snap = rt.pin(st, "nid").host
    _d2v(snap)
    assert not snap._d2v_identity

    sources = vids[:3]
    rows, _ = rt.traverse(st, "nid", sources, ["knows"], "out", 2)
    got = sorted(norm_edge(e) for (_, e, _) in rows)
    want = host_go(st, "nid", sources, ["knows"], "out", 2)
    assert got == want
    # every decoded endpoint is a real vid, not a dense id
    vidset = set(vids)
    for (sv, e, dv) in rows:
        assert sv in vidset and dv in vidset

    # fused-yield columnar path + MATCH frame decode, device vs host
    src_list = ", ".join(map(str, sources))
    for q in (f"GO 2 STEPS FROM {src_list} OVER knows "
              f"YIELD src(edge) AS s, dst(edge) AS d, knows.w AS w",
              f"MATCH (a:person)-[e:knows]->(b) WHERE id(a) == {sources[0]} "
              f"RETURN id(a), id(b), e.w"):
        out = []
        for tpu_rt in (None, rt):
            eng = QueryEngine(st, tpu_runtime=tpu_rt)
            s = eng.new_session()
            eng.execute(s, "USE nid")
            rs = eng.execute(s, q)
            assert rs.error is None, f"{q} -> {rs.error}"
            out.append(sorted(map(repr, rs.data.rows)))
        assert out[0] == out[1], q


def test_shared_runtime_two_stores_no_cache_collision(rt):
    """One TpuRuntime serving two DIFFERENT stores whose same-named
    spaces share an epoch value must not serve store A's pinned graph
    for store B's queries — the snapshot cache is keyed by space uid,
    not just (name, epoch)."""
    stores = [random_store(seed) for seed in (21, 22)]
    wants = [host_go(st, "g", [3, 17], ["knows"], "out", 2)
             for st in stores]
    assert wants[0] != wants[1]          # distinct graphs
    rows, _ = rt.traverse(stores[0], "g", [3, 17], ["knows"], "out", 2)
    assert sorted(norm_edge(e) for (_, e, _) in rows) == wants[0]
    # force the epoch COLLISION the uid guard exists for: store B's
    # same-named space reports the exact epoch store A was pinned at
    stores[1].space("g").epoch = stores[0].space("g").epoch
    assert stores[1].space("g").epoch == stores[0].space("g").epoch
    rows, _ = rt.traverse(stores[1], "g", [3, 17], ["knows"], "out", 2)
    assert sorted(norm_edge(e) for (_, e, _) in rows) == wants[1]


def _hubby_store(seed=2, n=120, extra=60):
    st = random_store(seed, n=n, avg_deg=5)
    rng = random.Random(9)
    for _ in range(extra):
        st.insert_edge("g", 7, "knows", rng.randrange(n),
                       rng.randint(0, 2),
                       {"w": rng.randint(0, 99), "f": 0.5, "tag": "ann"})
    return st


def test_degree_split_transform_layout():
    """degree_split preserves every (src, nbr, rank, props) tuple while
    spreading hub adjacency across parts as extra hub rows."""
    from nebula_tpu.graphstore.csr import build_snapshot, degree_split
    st = _hubby_store()
    snap = build_snapshot(st, "g")
    sp = degree_split(snap, threshold=16)
    assert sp.hub_dense is not None and len(sp.hub_dense) >= 1
    H = len(sp.hub_dense)
    vmax = snap.vmax
    for key in snap.blocks:
        b0, b1 = snap.blocks[key], sp.blocks[key]
        assert b1.indptr.shape == (P, vmax + H + 1)
        assert b0.total_edges() == b1.total_edges(), key

        def adj(b, hubs=None):
            out = {}
            nrows = vmax if hubs is None else vmax + len(hubs)
            for p in range(P):
                for r in range(nrows):
                    s, e = int(b.indptr[p, r]), int(b.indptr[p, r + 1])
                    if e <= s:
                        continue
                    dn = (r * P + p if r < vmax
                          else int(hubs[r - vmax]))
                    out.setdefault(dn, []).extend(
                        zip(b.nbr[p, s:e].tolist(),
                            b.rank[p, s:e].tolist(),
                            b.props["w"][p, s:e].tolist()))
            return {k: sorted(v) for k, v in out.items()}
        assert adj(b0) == adj(b1, sp.hub_dense), key


def test_degree_split_device_parity(rt):
    """GO / predicate / MATCH var-len / SHORTEST PATH / SUBGRAPH give
    identical rows with the supernode degree-split active (SURVEY §7
    hard-part #4's split option)."""
    from nebula_tpu.utils.config import get_config
    get_config().set_dynamic("tpu_degree_split_threshold", 8)
    try:
        st = _hubby_store()
        dev = rt.pin(st, "g", force=True)
        assert dev.host.hub_dense is not None \
            and len(dev.host.hub_dense) > 0
        for steps, direction in ((1, "out"), (2, "in"), (3, "both")):
            rows, _ = rt.traverse(st, "g", [3, 7, 44], ["knows"],
                                  direction, steps)
            got = sorted(norm_edge(e) for (_, e, _) in rows)
            assert got == host_go(st, "g", [3, 7, 44], ["knows"],
                                  direction, steps), (steps, direction)
        eng = QueryEngine(st, tpu_runtime=rt)
        s = eng.new_session()
        eng.execute(s, "USE g")
        plain = QueryEngine(st)
        sp = plain.new_session()
        plain.execute(sp, "USE g")
        for q in [
            "GO 2 STEPS FROM 7 OVER knows WHERE knows.w > 30 "
            "YIELD src(edge), dst(edge), knows.w",
            "MATCH (a:person)-[e:knows*1..2]->(b) WHERE id(a) == 7 "
            "RETURN count(*)",
            "FIND SHORTEST PATH FROM 7 TO 44 OVER knows YIELD path AS p",
            "GET SUBGRAPH 2 STEPS FROM 7 YIELD VERTICES AS nodes",
        ]:
            a, b = eng.execute(s, q), plain.execute(sp, q)
            assert a.error is None and b.error is None, \
                (q, a.error, b.error)
            assert sorted(map(repr, a.data.rows)) == \
                sorted(map(repr, b.data.rows)), q
    finally:
        get_config().set_dynamic("tpu_degree_split_threshold", 0)


def test_degree_split_bfs_parity(rt):
    """Device BFS distances with hubs == host level-synchronous BFS,
    on the sharded mesh AND the single-chip direction-optimizing
    variant (its bottom-up probes hub rows owned by other parts)."""
    import numpy as np
    from nebula_tpu.utils.config import get_config
    get_config().set_dynamic("tpu_degree_split_threshold", 8)
    try:
        st = _hubby_store(seed=4, n=150, extra=70)
        want = {3: 0}
        frontier = {3}
        for lvl in range(1, 6):
            nxt = set()
            for (sv, et, rank, dv, props, sgn) in st.get_neighbors(
                    "g", sorted(frontier), ["knows"], "out"):
                if dv not in want:
                    nxt.add(dv)
            for v in nxt:
                want[v] = lvl
            frontier = nxt
        sd = st.space("g")
        for runtime in (rt, TpuRuntime(make_mesh(1))):
            dev = runtime.pin(st, "g", force=True)
            assert dev.host.hub_dense is not None
            dist, _ = runtime.bfs(st, "g", [3], ["knows"], "out", 5)
            got = np.asarray(dist, np.int32)
            for vid in range(150):
                d = sd.dense_id(vid)
                if d < 0:
                    continue
                assert got[d % P, d // P] == want.get(vid, -1), vid
    finally:
        get_config().set_dynamic("tpu_degree_split_threshold", 0)


def test_degree_split_string_vids(rt):
    """Degree-split + FIXED_STRING vids: the d2v decode is an OBJECT
    array here (no identity fast path), and hub dense ids still map
    back to string vids exactly."""
    from nebula_tpu.utils.config import get_config
    get_config().set_dynamic("tpu_degree_split_threshold", 4)
    try:
        st = GraphStore()
        st.create_space("svh", partition_num=P,
                        vid_type="FIXED_STRING(16)")
        st.catalog.create_tag("svh", "person",
                              [PropDef("name", PropType.STRING)])
        st.catalog.create_edge("svh", "knows",
                               [PropDef("w", PropType.INT64)])
        rng = random.Random(3)
        vids = [f"v{i:03d}" for i in range(60)]
        for v in vids:
            st.insert_vertex("svh", v, "person", {"name": v})
        for v in vids:
            for _ in range(rng.randint(1, 4)):
                st.insert_edge("svh", v, "knows", rng.choice(vids), 0,
                               {"w": rng.randint(0, 9)})
        for i in range(25):
            st.insert_edge("svh", "v000", "knows", rng.choice(vids), i,
                           {"w": 1})
        dev = rt.pin(st, "svh", force=True)
        assert dev.host.hub_dense is not None
        rows, _ = rt.traverse(st, "svh", ["v000", "v005"], ["knows"],
                              "out", 2)
        got = sorted(norm_edge(e) for (_, e, _) in rows)
        want = host_go(st, "svh", ['"v000"', '"v005"'], ["knows"],
                       "out", 2)
        assert got == want
        for (sv, e, dv) in rows:
            assert isinstance(sv, str) and isinstance(dv, str)
    finally:
        get_config().set_dynamic("tpu_degree_split_threshold", 0)


def test_speculative_fetch_round_trips_and_undershoot(rt):
    """Repeat query shapes collapse the two-phase result fetch into ONE
    device_get (a device round trip saved per query); an undershoot —
    the kept set growing past the speculated prefix — falls back to the
    exact refetch with identical rows."""
    from nebula_tpu.tpu import fetch as R
    st = GraphStore()
    st.create_space("sf", partition_num=P, vid_type="INT64")
    st.catalog.create_tag("sf", "person", [PropDef("a", PropType.INT64)])
    st.catalog.create_edge("sf", "knows", [PropDef("w", PropType.INT64)])
    for v in range(60):
        st.insert_vertex("sf", v, "person", {"a": v})
    st.insert_edge("sf", 1, "knows", 2, 0, {"w": 1})
    st.insert_edge("sf", 1, "knows", 3, 0, {"w": 2})
    for i in range(40):                    # supersized vertex 2
        st.insert_edge("sf", 2, "knows", (i * 7) % 60, i, {"w": i})

    calls = [0]
    orig = R.jax.device_get

    def counting(x):
        calls[0] += 1
        return orig(x)

    R.jax.device_get = counting
    try:
        rows, _ = rt.traverse(st, "sf", [1], ["knows"], "out", 1)
        calls[0] = 0
        rows, _ = rt.traverse(st, "sf", [1], ["knows"], "out", 1)
        assert calls[0] == 1, calls[0]     # speculation engaged
        assert sorted(norm_edge(e) for (_, e, _) in rows) == \
            host_go(st, "sf", [1], ["knows"], "out", 1)
        # same program shape, 20x the kept set: speculated prefix is
        # too small — exact refetch kicks in, rows still identical
        rows, _ = rt.traverse(st, "sf", [2], ["knows"], "out", 1)
        assert sorted(norm_edge(e) for (_, e, _) in rows) == \
            host_go(st, "sf", [2], ["knows"], "out", 1)
        calls[0] = 0
        rows, _ = rt.traverse(st, "sf", [2], ["knows"], "out", 1)
        assert calls[0] == 1               # re-armed at the larger size
        assert sorted(norm_edge(e) for (_, e, _) in rows) == \
            host_go(st, "sf", [2], ["knows"], "out", 1)
    finally:
        R.jax.device_get = orig


def test_over_all_direction_combos_parity(rt):
    """OVER * x REVERSELY/BIDIRECT x m-to-n: multi-block expansion in
    every direction matches the host engine row-for-row."""
    st = random_store(11, extra_edge_type=True)
    eng = QueryEngine(st, tpu_runtime=rt)
    s = eng.new_session()
    eng.execute(s, "USE g")
    plain = QueryEngine(st)
    sp = plain.new_session()
    plain.execute(sp, "USE g")
    for q in ["GO 2 STEPS FROM 3, 7 OVER * REVERSELY "
              "YIELD src(edge), dst(edge), rank(edge)",
              "GO 2 STEPS FROM 3, 7 OVER * BIDIRECT "
              "YIELD src(edge), dst(edge)",
              "GO 1 TO 3 STEPS FROM 3 OVER * YIELD dst(edge) AS d",
              "GO 2 STEPS FROM 3 OVER knows, likes REVERSELY "
              "YIELD type(edge), dst(edge)"]:
        a, b = eng.execute(s, q), plain.execute(sp, q)
        assert a.error is None and b.error is None, (q, a.error, b.error)
        assert sorted(map(repr, a.data.rows)) == \
            sorted(map(repr, b.data.rows)), q
