"""The hop's by-need loops (hop.py `_by_need`, PR 25): a program whose
per-slot stages run over `ceil(need / chunk)` chunks returns what the
straight-line program returns — kcount, every captured key up to its
kept count (the four identity arrays everywhere), hop_edges,
frontier_sizes, ovf_expand and the next frontier — and its engagement
counters say how many chunks it ran.

The chunk is small here through the builders' `chunk` argument (the
module constant is 2^14 slots); the straight-line program is the same
builder with a chunk no budget exceeds.
"""
import jax
import numpy as np
import pytest

from nebula_tpu.exec.engine import QueryEngine
from nebula_tpu.utils.config import get_config
from nebula_tpu.utils.stats import stats

tpu = pytest.importorskip("nebula_tpu.tpu")
from nebula_tpu.tpu import TpuRuntime, make_mesh, runtime    # noqa: E402
from nebula_tpu.tpu import hop                               # noqa: E402
from nebula_tpu.tpu.device import join_halves, split_halves  # noqa: E402

from test_delta import store_p                               # noqa: E402
from test_tpu import _hubby_store                            # noqa: E402

C, EB = 16, 64
STRAIGHT = 1 << 30
P, VMAX, E = 2, 24, 256
# part 0's local vertex i expands DEGS[i] edges: one frontier vertex
# gives a hop of exactly that size
DEGS = [0, 1, C - 1, C, C + 1, EB, EB + 5]
IDENT = ("src", "dst", "rank", "eidx")
META = ("frontier", "fcount", "hop_edges", "frontier_sizes", "ovf_expand",
        "kcount")
# the four layouts of the one entry `hop.build_traverse_fn(mesh or None,
# ..., lanes=)`, as (on a mesh, lane-batched)
LAYOUTS = [pytest.param((True, False), id="mesh"),
           pytest.param((False, False), id="one-chip"),
           pytest.param((False, True), id="one-chip-lanes"),
           pytest.param((True, True), id="mesh-lanes")]


def _block(seed=5):
    """One CSR block over P parts as the kernels take it (runtime.py
    `blocks_data`): part 0 holds DEGS, part 1 small random rows."""
    rng = np.random.default_rng(seed)
    deg = np.zeros((P, VMAX), np.int64)
    deg[0, :len(DEGS)] = DEGS
    deg[1, 1:] = rng.integers(0, 4, VMAX - 1)      # part 1's vertex 0: none
    indptr = np.zeros((P, VMAX + 1), np.int32)
    indptr[:, 1:] = np.cumsum(deg, axis=1)
    assert indptr[:, -1].max() <= E
    return {"indptr": indptr,
            "nbr": rng.integers(0, P * VMAX, (P, E)).astype(np.int32),
            "rank": rng.integers(0, 3, (P, E)).astype(np.int32),
            # as pinned: a column's 32-bit halves (P, 2, E)
            "props": {"w": split_halves(rng.integers(-5, 100, (P, E))),
                      "f": split_halves(rng.uniform(0, 1, (P, E)))}}


def _frontier(total):
    """The part-0 vertex that expands `total` edges (and, beside it, a
    small part-1 row unless the hop is to stay empty)."""
    f = np.zeros((P, VMAX), bool)
    f[0, DEGS.index(total)] = True
    f[1, 1 if total else 0] = True
    return f


def _halves_same(g, w, kc, tag):
    """A captured property column, lead + (nb, 2, W) halves, is the
    same in both programs up to each row's kept count."""
    assert g.dtype == np.uint32 and g.shape[-2] == 2, tag
    live = np.broadcast_to(
        (np.arange(g.shape[-1]) < kc[..., None])[..., None, :], g.shape)
    assert np.array_equal(g[live], w[live]), tag


def _same(got, want, tag=""):
    """Every leaf the host reads is the same in both programs."""
    got, want = jax.device_get(got), jax.device_get(want)
    for k in META:
        assert np.array_equal(got[k], want[k]), (tag, k)
    kc = got["kcount"]
    for k, w in want["cap"].items():
        g = got["cap"][k]
        assert g.dtype == w.dtype and g.shape == w.shape, (tag, k)
        if k in IDENT:
            assert np.array_equal(g, w), (tag, k)
        else:
            _halves_same(g, w, kc, (tag, k))
    assert not want["chunks_run"].any() and not want["chunks_budget"].any()
    assert (got["chunks_run"] <= got["chunks_budget"]).all(), tag
    return got


def _w_over_50(cols):
    return join_halves(cols["w"], np.int64) > 50


@pytest.mark.parametrize("with_pred", [False, True],
                         ids=["all", "pred"])
@pytest.mark.parametrize("total", DEGS)
def test_one_hop_of_exact_size(total, with_pred):
    """total = 0, 1, C-1, C, C+1, EB and > EB (overflow; then the
    ladder's retry at the doubled budget)."""
    kw = dict(yield_cols=("f", "w"))
    if with_pred:
        kw.update(pred=_w_over_50, pred_cols=("w",))
    blocks, frontier = (_block(),), _frontier(total)
    eb = EB
    while True:
        got = _same(
            hop.build_traverse_fn(None, P, eb, 1, 1, chunk=C, **kw)(
                blocks, frontier),
            hop.build_traverse_fn(None, P, eb, 1, 1, chunk=STRAIGHT, **kw)(
                blocks, frontier), (total, eb))
        assert got["hop_edges"][0, 0] == total
        need = -(-min(total, eb) // C)             # chunks the hop fills
        kept = -(-int(got["kcount"].max()) // C)
        # the expansion's gathers run to the fill and the property
        # gathers to the kept count; so does the compaction between
        # them, which an unfiltered hop skips (its fill IS the prefix)
        loops = 3 if with_pred else 2
        assert (got["chunks_run"] == (loops - 1) * need + kept).all()
        assert (got["chunks_budget"] == loops * (eb // C)).all()
        if not with_pred:
            assert kept == need
        if not got["ovf_expand"].any():
            break
        assert total > eb
        eb *= 2


def test_hops_of_different_budgets_feed_each_other():
    """Three hops, each with its own budget: the marks of a chunked hop
    are the next hop's frontier (hop_edges and frontier_sizes agree per
    hop), and a hop whose budget fits one chunk stays straight-line."""
    ebs = (C, 4 * C, 16 * C)
    blocks = (_block(), _block(9))
    frontier = np.zeros((P, VMAX), bool)
    frontier[:, 1:5] = True
    kw = dict(pred=_w_over_50, pred_cols=("w", "_rank"), yield_cols=("w",))
    got = _same(
        hop.build_traverse_fn(None, P, ebs, 3, 2, chunk=C, **kw)(
            blocks, frontier),
        hop.build_traverse_fn(None, P, ebs, 3, 2, chunk=STRAIGHT, **kw)(
            blocks, frontier))
    assert (got["chunks_budget"][:, 0] == 0).all()
    assert (got["chunks_budget"][:, 1] == 2 * 4).all()       # no capture
    assert (got["chunks_budget"][:, 2] == 2 * 3 * 16).all()
    assert got["hop_edges"][:, 2].max() > C


def test_lanes_vmap_runs_to_the_fullest_lane():
    """The lane program: one loop over lanes whose trip counts differ."""
    totals = [0, 1, C + 1, EB + 5]
    blocks = (_block(),)
    frontier = np.stack([_frontier(t) for t in totals])
    kw = dict(yield_cols=("f", "w"))
    got = _same(
        hop.build_traverse_fn(None, P, EB, 1, 1, lanes=True, chunk=C,
                              **kw)(
            blocks, frontier),
        hop.build_traverse_fn(None, P, EB, 1, 1, lanes=True,
                              chunk=STRAIGHT, **kw)(
            blocks, frontier))
    assert list(got["hop_edges"][:, 0, 0]) == totals
    assert list(got["chunks_run"][:, 0, 0]) == [0, 2, 4, 8]


@pytest.mark.parametrize("capture_hops", [False, True],
                         ids=["go", "frames"])
def test_two_shard_mesh(capture_hops):
    """Inside shard_map each shard runs its own trip count."""
    from jax.sharding import Mesh
    mesh = Mesh(np.asarray(jax.devices()[:P]), ("part",))
    blocks = (_block(),)
    frontier = _frontier(C + 1)
    kw = dict(pred=_w_over_50, pred_cols=("w",), capture_hops=capture_hops,
              yield_cols=() if capture_hops else ("f",))
    got = _same(
        hop.build_traverse_fn(mesh, P, EB, 2, 1, chunk=C, **kw)(
            blocks, frontier),
        hop.build_traverse_fn(mesh, P, EB, 2, 1, chunk=STRAIGHT, **kw)(
            blocks, frontier))
    run = got["chunks_run"][:, 0]
    assert run[0] > run[1] > 0          # part 0 filled C+1, part 1 three


def test_lanes_by_shards_grid():
    from jax.sharding import Mesh
    mesh = Mesh(np.asarray(jax.devices()[:2 * P]).reshape(2, P),
                ("lane", "part"))
    blocks = (_block(),)
    frontier = np.stack([_frontier(t) for t in (1, EB, C - 1, 0)])
    kw = dict(pred=_w_over_50, pred_cols=("w",), yield_cols=("w",))
    _same(
        hop.build_traverse_fn(mesh, P, EB, 2, 1, lanes=True, chunk=C,
                              **kw)(blocks, frontier),
        hop.build_traverse_fn(mesh, P, EB, 2, 1, lanes=True,
                              chunk=STRAIGHT, **kw)(blocks, frontier))


# -- the runtime's own inputs: delta plane, hubs, frames, the ladder ------


@pytest.fixture()
def paired(monkeypatch):
    """Every traverse program the runtime builds becomes a pair — the
    chunked program (chunk 4) and the straight-line one on the same
    inputs — that must agree leaf by leaf before the chunked result goes
    on to the ladder, the fetch and the materialisers.  Yields the list
    of (chunks run, chunks budgeted, overflowed) per dispatch."""
    seen = []

    def pair(build):
        def both(*a, **kw):
            chunked = build(*a, chunk=4, **kw)
            straight = build(*a, chunk=STRAIGHT, **kw)

            def fn(blocks, frontier):
                got = chunked(blocks, frontier)
                host = _same(got, straight(blocks, frontier), build.__name__)
                seen.append((int(host["chunks_run"].sum()),
                             int(host["chunks_budget"].sum()),
                             bool(host["ovf_expand"].any())))
                return got
            return fn
        return both
    monkeypatch.setattr(runtime, "build_traverse_fn",
                        pair(hop.build_traverse_fn))
    return seen


def _small_budget(parts):
    rt = TpuRuntime(make_mesh(parts))
    rt.init_eb = 16         # four chunks of 4; the ladder climbs from here
    return rt


def _rows(eng, q, space="g"):
    s = eng.new_session()
    assert eng.execute(s, f"USE {space}").error is None
    rs = eng.execute(s, q)
    assert rs.error is None, (q, rs.error)
    return sorted(map(repr, rs.data.rows))


GO_Q = ("GO 2 STEPS FROM 1, 2, 3, 4, 5, 6, 8, 9 OVER knows WHERE knows.w > 10 "
        "YIELD src(edge), dst(edge), rank(edge), knows.w, knows.f")


@pytest.mark.parametrize("parts", [1, 2], ids=["one-chip", "two-shards"])
def test_delta_plane_and_ladder_retry(paired, parts):
    """Writes after the pin ride the delta plane (capture EB + Dcap
    wide, tombstones tested per slot); the 16-slot budget overflows, so
    the ladder retries at a larger chunked program."""
    cfg = get_config()
    cfg.set_dynamic_many({"tpu_delta_max_edges": 64,
                          "tpu_delta_compact_watermark": 2.0})
    try:
        st = store_p(parts)
        rt = _small_budget(parts)
        eng = QueryEngine(st, tpu_runtime=rt)
        assert _rows(eng, GO_Q) == _rows(QueryEngine(st), GO_Q)
        assert any(ovf for _, _, ovf in paired), "the ladder never climbed"
        pins = stats().snapshot().get("tpu_pins", 0)
        for v in (1, 2, 3):
            st.insert_edge("g", v, "knows", 40 + v, 0,
                           {"w": 60, "f": 0.5, "tag": "ann"})
        src, _, rank, dst, _, _ = next(iter(
            st.get_neighbors("g", [1], ["knows"], "out")))
        st.delete_edge("g", src, "knows", dst, rank)
        del paired[:]
        assert _rows(eng, GO_Q) == _rows(QueryEngine(st), GO_Q)
        assert stats().snapshot().get("tpu_pins", 0) == pins, \
            "the writes re-pinned: the delta plane was not exercised"
        assert paired and all(r <= b and b > 0 for r, b, _ in paired)
    finally:
        with cfg.lock:
            for k in ("tpu_delta_max_edges", "tpu_delta_compact_watermark"):
                cfg.dynamic_layer.pop(k, None)


@pytest.mark.parametrize("q", [
    GO_Q.replace("1, 2, 3, 4, 5, 6, 8, 9", "7"),
    "MATCH (a:person)-[e:knows*1..2]->(b) WHERE id(a) == 7 RETURN count(*)",
    "GET SUBGRAPH 2 STEPS FROM 7 YIELD VERTICES AS nodes",
], ids=["go", "match-frames", "subgraph-frames"])
def test_degree_split_hubs_and_frames(paired, q):
    """Hub rows after the local rows (degree-split snapshot), and the
    capture_hops programs MATCH and GET SUBGRAPH run."""
    cfg = get_config()
    cfg.set_dynamic("tpu_degree_split_threshold", 8)
    try:
        st = _hubby_store()
        rt = _small_budget(1)
        dev = rt.pin(st, "g", force=True)
        assert len(dev.host.hub_dense) > 0
        assert _rows(QueryEngine(st, tpu_runtime=rt), q) == \
            _rows(QueryEngine(st), q)
        assert paired and any(r > 0 for r, _, _ in paired)
    finally:
        cfg.set_dynamic("tpu_degree_split_threshold", 0)


# -- the engagement counters ------------------------------------------------


def test_chunk_counters_move_only_when_a_loop_ran(monkeypatch):
    """`tpu_hop_chunks_run` <= `tpu_hop_chunks_budget`; both move on a
    statement whose budget exceeds the chunk and neither moves when it
    fits one (the default chunk is 2^14 slots, the default budget 2048),
    and `TraverseStats` carries the statement's own share."""
    def moved():
        snap = stats().snapshot()
        return (snap.get("tpu_hop_chunks_run", 0),
                snap.get("tpu_hop_chunks_budget", 0))
    st = store_p(1)
    vids = [1, 2, 3]
    rt = TpuRuntime(make_mesh(1))
    before = moved()
    rows, ts = rt.traverse(st, "g", vids, ["knows"], "out", 2)
    assert rows and moved() == before
    assert (ts.chunks_run, ts.chunks_budget) == (0, 0)

    small = hop.build_traverse_fn
    monkeypatch.setattr(
        runtime, "build_traverse_fn",
        lambda *a, **kw: small(*a, chunk=64, **kw))
    rt2 = TpuRuntime(make_mesh(1))
    rows2, ts2 = rt2.traverse(st, "g", vids, ["knows"], "out", 2)
    run, budget = (a - b for a, b in zip(moved(), before))
    assert len(rows2) == len(rows)
    assert 0 < run <= budget
    assert (ts2.chunks_run, ts2.chunks_budget) == (run, budget)
    # two hops of 2048 slots in 64-slot chunks: the expansion's loop on
    # each (nothing is filtered or yielded, so no other loop)
    assert budget == 2 * (2048 // 64)
