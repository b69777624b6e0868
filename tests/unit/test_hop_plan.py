"""The hop's expansion plan laid out from the frontier's members
(hop.py `_expand_plan`, PR 29): over a bitmap wider than `plan_chunk`
the plan's scatters are sized by the words of the bitmap that hold an
expanding vertex, and the program returns what the whole-bitmap program
returns — every key of META, the identity arrays, `prop:*` up to the
kept count — while `plan_run` / `plan_budget` say what it issued.

The blocks are the runtime's own, captured from a pinned store (plain,
degree-split with hub rows, delta plane live); the frontiers are made
here.  The threshold is small through the builder's `plan_chunk`
argument (the module constant is 2^14 ids); the whole-bitmap program is
the same layout with a `plan_chunk` no bitmap exceeds.
"""
import functools

import jax
import numpy as np
import pytest

from nebula_tpu.exec.engine import QueryEngine
from nebula_tpu.utils.config import get_config
from nebula_tpu.utils.stats import stats

tpu = pytest.importorskip("nebula_tpu.tpu")
from nebula_tpu.tpu import TpuRuntime, make_mesh, runtime    # noqa: E402
from nebula_tpu.tpu import hop                               # noqa: E402

from test_delta import store_p                               # noqa: E402
from test_hop_by_need import (GO_Q, IDENT, LAYOUTS, META,      # noqa: E402
                              _halves_same, _rows)
from test_tpu import _hubby_store                            # noqa: E402

PC = 64                 # two words of 32 ids a trip
WHOLE = 1 << 30
EBS = (2048, 2048)
PLANES = ("plain", "hubs", "delta", "armed")
FRONTIERS = ("empty", "one", "sparse", "every", "degree0", "one-part")
# "plain" holds no delta plane (the flag's explicit 0); "armed" is the
# default flags' plane, armed at the capacity a pin works out and empty
FLAGS = {"plain": {"tpu_delta_max_edges": 0},
         "hubs": {"tpu_degree_split_threshold": 8},
         "delta": {"tpu_delta_max_edges": 64,
                   "tpu_delta_compact_watermark": 2.0},
         "armed": {}}


@functools.lru_cache(maxsize=None)
def _plane(name):
    """The kernel inputs of one pinned store, as the runtime hands them
    to a traverse program: (blocks on the host, builder keywords, P)."""
    seen = []
    real = hop.build_traverse_fn

    def capturing(*a, **kw):
        fn = real(*a, **kw)

        def run(blocks, frontier):
            seen.append(({k: v for k, v in kw.items() if k != "lanes"},
                         jax.device_get(blocks)))
            return fn(blocks, frontier)
        return run
    cfg = get_config()
    cfg.set_dynamic_many(FLAGS.get(name, {}))
    runtime.build_traverse_fn = capturing
    try:
        st = _hubby_store(n=600) if name == "hubs" else store_p(2, n=600)
        rt = TpuRuntime(make_mesh(1))
        eng = QueryEngine(st, tpu_runtime=rt)
        _rows(eng, GO_Q)
        if name == "delta":
            pins = stats().snapshot().get("tpu_pins", 0)
            for v in (1, 2, 3):
                st.insert_edge("g", v, "knows", 40 + v, 0,
                               {"w": 60, "f": 0.5, "tag": "ann"})
            src, _, rank, dst, _, _ = next(iter(
                st.get_neighbors("g", [1], ["knows"], "out")))
            st.delete_edge("g", src, "knows", dst, rank)
            _rows(eng, GO_Q)
            assert stats().snapshot().get("tpu_pins", 0) == pins
    finally:
        runtime.build_traverse_fn = real
        with cfg.lock:
            for k in FLAGS.get(name, {}):
                cfg.dynamic_layer.pop(k, None)
    kw, blocks = seen[-1]
    P = blocks[0]["indptr"].shape[0]
    if name == "hubs":
        assert len(kw["hub_dense"]) > 0
    if name == "delta":
        assert blocks[0]["d_src"].shape[-1] and blocks[0]["d_valid"].any()
    if name == "armed":
        assert blocks[0]["d_src"].shape[-1] and not blocks[0]["d_valid"].any()
    if name == "plain":
        assert "d_src" not in blocks[0]
    return blocks, kw, P


def _vmax(blocks, kw):
    hubs = 0 if kw.get("hub_dense") is None else len(kw["hub_dense"])
    return blocks[0]["indptr"].shape[1] - 1 - hubs


def _frontier(case, blocks, kw, P, seed=0):
    vmax = _vmax(blocks, kw)
    deg = np.diff(blocks[0]["indptr"][:, :vmax + 1], axis=1)
    rng = np.random.default_rng(seed)
    f = np.zeros((P, vmax), bool)
    if case == "one":
        p, v = np.argwhere(deg > 0)[3]
        f[p, v] = True
    elif case == "sparse":
        f = rng.random((P, vmax)) < 0.05
    elif case == "every":
        f[:] = True
    elif case == "degree0":
        p, v = np.argwhere(deg == 0)[0]
        f[p, v] = True
        f[(p + 1) % P, :5] = True
    elif case == "one-part":
        f[0] = rng.random(vmax) < 0.2
    else:
        assert case == "empty"
    return f


@functools.lru_cache(maxsize=None)
def _program(layout, plane, plan_chunk, ebs=EBS):
    blocks, kw, P = _plane(plane)
    meshed, lanes = layout
    mesh = None
    if meshed:
        rows = 2 if lanes and 2 * P <= 8 else 1
        devs = np.asarray(jax.devices()[:rows * P])
        from jax.sharding import Mesh
        mesh = (Mesh(devs.reshape(rows, P), ("lane", "part"))
                if lanes else Mesh(devs, ("part",)))
    return hop.build_traverse_fn(mesh, P, ebs, len(ebs), len(blocks),
                                 lanes=lanes, plan_chunk=plan_chunk, **kw)


def _same(got, want, tag):
    got, want = jax.device_get(got), jax.device_get(want)
    for k in META:
        assert np.array_equal(got[k], want[k]), (tag, k)
    kc = got["kcount"]
    assert set(got["cap"]) == set(want["cap"])
    for k, w in want["cap"].items():
        g = got["cap"][k]
        assert g.dtype == w.dtype and g.shape == w.shape, (tag, k)
        if k in IDENT:
            assert np.array_equal(g, w), (tag, k)
        else:
            _halves_same(g, w, kc, (tag, k))
    for k in ("chunks_run", "chunks_budget"):
        assert np.array_equal(got[k], want[k]), (tag, k)
    assert not want["plan_run"].any() and not want["plan_budget"].any()
    return got


def _inputs(layout, case, plane):
    blocks, kw, P = _plane(plane)
    f = _frontier(case, blocks, kw, P)
    if layout[1]:               # the case beside a sparse lane
        f = np.stack([f, _frontier("sparse", blocks, kw, P, seed=1)])
    return blocks, f


@pytest.mark.parametrize("plane", PLANES)
@pytest.mark.parametrize("case", FRONTIERS)
@pytest.mark.parametrize("layout", LAYOUTS)
def test_member_plan_returns_what_the_whole_bitmap_plan_returns(
        layout, case, plane):
    blocks, f = _inputs(layout, case, plane)
    got = _same(_program(layout, plane, PC)(blocks, f),
                _program(layout, plane, WHOLE)(blocks, f),
                (layout, case, plane))
    width = blocks[0]["indptr"].shape[1] - 1      # hub rows and all
    assert (got["plan_budget"] == 2 * width * len(blocks)).all()
    assert (got["plan_run"] > 0).all()
    assert (got["plan_run"] < got["plan_budget"]).all()
    if case != "empty":
        assert got["hop_edges"].sum() > 0
    if case == "every" and not layout[1]:
        assert got["frontier_sizes"][..., 0].sum() == f.sum()


@pytest.mark.parametrize("plane", PLANES)
@pytest.mark.parametrize("layout", LAYOUTS)
def test_more_expanding_vertices_than_slots_flags_the_overflow(
        layout, plane):
    """EB = 16 under a frontier of every vertex: more members than
    slots.  Only those whose first slot lies below EB are listed; the
    flag is raised, the rows are the whole-bitmap program's and no
    index leaves its table."""
    blocks, f = _inputs(layout, "every", plane)
    ebs = (16, 16)
    got = _same(_program(layout, plane, PC, ebs)(blocks, f),
                _program(layout, plane, WHOLE, ebs)(blocks, f),
                (layout, plane))
    lane0 = (0,) if layout[1] else ()             # the every-vertex lane
    assert got["ovf_expand"][lane0].all()
    assert (got["hop_edges"][lane0][..., 0] > 16).all()
    emax = blocks[0]["nbr"].shape[-1] + (
        blocks[0]["d_src"].shape[-1] if "d_src" in blocks[0] else 0)
    eidx = got["cap"]["eidx"]
    assert eidx.min() >= 0 and eidx.max() < emax


def _host_plan_updates(indptr, f, EB, plan_chunk, together):
    """What `_plan_members` issues for one block, counted from the
    frontier: an update a word for the list, then whole trips of
    `plan_chunk` lane updates until the listed words are done — the
    trips of the fullest part when the parts share one loop."""
    B = hop.PLAN_BLOCK
    P, width = f.shape
    W, CW = -(-width // B), max(plan_chunk // B, 1)
    deg = np.where(f, np.diff(indptr, axis=1), 0)
    starts = np.cumsum(deg, axis=1) - deg
    member = (deg > 0) & (starts < EB)
    words = np.array([np.unique(np.flatnonzero(m) // B).size for m in member])
    looped = -(-min(W, EB) // CW) > 1
    trips = -(-words // CW) if looped else np.ones(P, int)
    if together:
        trips = np.full(P, trips.max())
    return W + trips * CW * B


@pytest.mark.parametrize("case", FRONTIERS)
@pytest.mark.parametrize("layout", LAYOUTS[:2])
def test_plan_counters_count_the_updates_issued(layout, case):
    """One chip runs the parts in one loop, to the fullest part's trip
    count; a shard runs its own."""
    blocks, kw, P = _plane("plain")
    f = _frontier(case, blocks, kw, P)
    got = jax.device_get(_program(layout, "plain", PC)(blocks, f))
    want = _host_plan_updates(blocks[0]["indptr"], f, EBS[0], PC,
                              together=not layout[0])
    assert got["plan_run"][:, 0].tolist() == want.tolist()
    assert (got["plan_budget"] == 2 * f.shape[1]).all()
    if case == "every":     # every word listed: a scatter's worth, plus the list
        W = -(-f.shape[1] // hop.PLAN_BLOCK)
        assert want.max() >= W * hop.PLAN_BLOCK + W


def test_a_narrow_bitmap_compiles_the_whole_bitmap_plan():
    """At the default `plan_chunk` (2^14) these 300-wide bitmaps take
    `_plan_whole`: the counters stay 0 and the program holds the two
    scatters with an update a local vertex."""
    blocks, kw, P = _plane("plain")
    f = _frontier("sparse", blocks, kw, P)
    assert f.shape[1] <= hop.PLAN_CHUNK
    fn = hop.build_traverse_fn(None, P, EBS, 2, len(blocks), **kw)
    got = jax.device_get(fn(blocks, f))
    assert not got["plan_run"].any() and not got["plan_budget"].any()
    assert got["plan_run"].shape == got["chunks_run"].shape == (P, 2)


# -- the BFS level bodies (algo/frontier.py), which lay their plans out the same way --


@pytest.mark.parametrize("case", FRONTIERS)
def test_a_level_body_under_vmap_takes_the_one_plan(monkeypatch, case):
    """`top_down_step` over the plain plane's block, every part under
    one vmap, in trips of 64 slots: the member plan (a bitmap wider than
    `PLAN_CHUNK`) and the whole-bitmap plan mark the same vertices and
    count the same edges, overflow flags and trips."""
    from nebula_tpu.algo import frontier
    blocks, kw, P = _plane("plain")
    f = _frontier(case, blocks, kw, P)
    vmax = _vmax(blocks, kw)
    pids = np.arange(P, dtype=np.int32)

    def run(plan_chunk):
        monkeypatch.setattr(hop, "PLAN_CHUNK", plan_chunk)
        return jax.device_get(jax.jit(
            lambda bs, fb: frontier.top_down_step(
                bs, fb, 1024, P, vmax, pids, chunk=64))(blocks, f))
    got, want = run(PC), run(WHOLE)
    for g, w in zip(got, want):
        assert np.array_equal(g, w)
    cand, edges, ovf, trips, budget = got
    assert cand.shape == (P, vmax) and (budget == 1024 // 64).all()
    assert (trips == -(-min(int(edges.max()), 1024) // 64)).all()
    assert cand.any() == bool(edges.sum())


# -- through the runtime: the counters, MATCH frames, BFS ---------------------


@pytest.fixture()
def small_plans(monkeypatch):
    """Every traverse program the runtime builds lays its plans out
    from the members (plan_chunk 32: one word a trip), and the BFS
    level bodies theirs (frontier.py reads `hop.PLAN_CHUNK` as it is
    traced)."""
    real = hop.build_traverse_fn
    monkeypatch.setattr(
        runtime, "build_traverse_fn",
        lambda *a, **kw: real(*a, plan_chunk=32, **kw))
    monkeypatch.setattr(hop, "PLAN_CHUNK", 32)


@pytest.mark.parametrize("parts", [1, 2], ids=["one-chip", "two-shards"])
@pytest.mark.parametrize("q", [
    GO_Q,
    "MATCH (a:person)-[e:knows*1..2]->(b) WHERE id(a) == 7 RETURN count(*)",
    "GET SUBGRAPH 2 STEPS FROM 7 YIELD VERTICES AS nodes",
    "FIND SHORTEST PATH FROM 7 TO 55 OVER knows UPTO 4 STEPS YIELD path AS p",
], ids=["go", "match-frames", "subgraph-frames", "bfs"])
def test_rows_through_the_runtime(small_plans, parts, q):
    st = store_p(parts, n=240)
    eng = QueryEngine(st, tpu_runtime=TpuRuntime(make_mesh(parts)))
    assert _rows(eng, q) == _rows(QueryEngine(st), q)


def test_plan_counters_move_only_over_a_wide_bitmap(monkeypatch):
    """`tpu_hop_plan_run` < `tpu_hop_plan_budget`; both move on a
    statement whose bitmap is wider than the threshold and neither
    moves under the default (2^14 ids against 240 here), and
    `TraverseStats` carries the statement's own."""
    def moved():
        snap = stats().snapshot()
        return (snap.get("tpu_hop_plan_run", 0),
                snap.get("tpu_hop_plan_budget", 0))
    st = store_p(1, n=240)
    vids = [1, 2, 3]
    before = moved()
    rt = TpuRuntime(make_mesh(1))
    rows, ts = rt.traverse(st, "g", vids, ["knows"], "out", 2)
    assert rows and moved() == before
    assert (ts.plan_run, ts.plan_budget) == (0, 0)

    small = hop.build_traverse_fn
    monkeypatch.setattr(
        runtime, "build_traverse_fn",
        lambda *a, **kw: small(*a, plan_chunk=64, **kw))
    rows2, ts2 = TpuRuntime(make_mesh(1)).traverse(
        st, "g", vids, ["knows"], "out", 2)
    run, budget = (a - b for a, b in zip(moved(), before))
    assert len(rows2) == len(rows)
    assert 0 < run < budget
    assert (ts2.plan_run, ts2.plan_budget) == (run, budget)
    # two hops over one part's 240 ids (and the rows the armed delta
    # plane keeps for new vertices): two scatters of that width each
    width = rt.snapshots["g"].vmax
    assert width == 240 + int(get_config().get("tpu_delta_vmax_slack"))
    assert budget == 2 * 2 * width


# -- by shapes alone: the four-chip cell's program at its full size ----------


def _indexed_ops(jaxpr, width, found):
    """Every gather or scatter equation of `jaxpr` and the jaxprs inside
    it whose number of index vectors (= its updates or results) is
    `width`."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "gather" or name.startswith("scatter"):
            indices = eqn.invars[1].aval
            if int(np.prod(indices.shape[:-1], dtype=np.int64)) == width:
                found.append((name, eqn.invars[0].aval.shape))
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _indexed_ops(sub, width, found)
    return found


@pytest.mark.parametrize("plan_chunk,want", [(WHOLE, 6), (None, 0)],
                         ids=["whole-bitmap", "members"])
def test_no_gather_or_scatter_is_as_wide_as_the_bitmap(plan_chunk, want):
    """`snb-sf300-proxy.go3-4chip`'s program (benchmarks/configs): one
    part of 1,500,000 local vertices a chip, budgets (2048, 8192,
    262144).  Laid out from the members, no gather or scatter of the
    3-hop program has an index per local vertex; the whole-bitmap plan
    has two a hop (the control: the walk sees what it should)."""
    from jax.sharding import Mesh
    P, vmax, width = 4, 1_500_000, 50_331_648
    assert vmax > hop.PLAN_CHUNK
    mesh = Mesh(np.asarray(jax.devices()[:P]), ("part",))
    kw = {} if plan_chunk is None else {"plan_chunk": plan_chunk}
    fn = hop.build_traverse_fn(mesh, P, (2048, 8192, 262144), 3, 1,
                               capture=True, yield_cols=("f", "w"), **kw)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)
    block = {"indptr": s((P, vmax + 1), np.int32),
             "nbr": s((P, width), np.int32), "rank": s((P, width), np.int32),
             "props": {"f": s((P, 2, width), np.uint32),
                       "w": s((P, 2, width), np.uint32)}}
    jaxpr = jax.make_jaxpr(fn)((block,), s((P, vmax), np.bool_))
    found = _indexed_ops(jaxpr.jaxpr, vmax, [])
    assert len(found) == want, found
    if want:
        assert {n for n, _ in found} == {"scatter", "scatter-add"}
    else:
        # the walk reaches inside the loops: a trip's row gather, one
        # index a listed word, from the words' table of lanes
        B = hop.PLAN_BLOCK
        rows = _indexed_ops(jaxpr.jaxpr, hop.PLAN_CHUNK // B, [])
        assert rows.count(("gather", (-(-vmax // B), B))) == 3, rows
