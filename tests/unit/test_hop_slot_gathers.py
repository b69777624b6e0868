"""The expansion loop gathers what a consumer reads (hop.py
`_expand_slots`, PR 38): a traverse program built without rank
(`build_traverse_fn(carry_rank=False)`) returns what the program built
with it returns, less the `rank` entry of its capture; the one
row-offset table (`_row_offsets`: `eidx = off[row] + j`) gives the edge
index `indptr[row] + (j - starts[row])` gave; the runtime keeps rank
wherever something reads it (`runtime.py` `_run_traverse`), and the
summary `tpu_hop_slot_gathers` says how many gathers a slot of the last
hop cost.

The blocks are the runtime's own, captured from a pinned store as
`test_hop_plan.py` captures them; the frontiers are made here.
"""
import functools
import random

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
from test_hop_by_need import _halves_same, _rows             # noqa: E402

PC = 64                 # the member plan: two words of 32 ids a trip
WHOLE = 1 << 30         # the whole-bitmap plan: no bitmap is wider
EBS = (2048, 2048)
YIELD = "YIELD dst(edge) AS d, knows.w AS w, knows.f AS f"
# what the rank-free program has to return as the other does
SAME = ("kcount", "hop_edges", "chunks_run", "plan_run", "ovf_expand",
        "frontier_sizes")


def _store(parts, hubs, n=600):
    st = store_p(parts, n=n)
    if hubs:
        rng = random.Random(9)
        for _ in range(60):
            st.insert_edge("g", 7, "knows", rng.randrange(n),
                           rng.randint(0, 2),
                           {"w": rng.randint(0, 99), "f": 0.5, "tag": "ann"})
    return st


def _flags(**kw):
    """Set dynamic flags now; returns the undo."""
    cfg = get_config()
    cfg.set_dynamic_many(kw)

    def undo():
        with cfg.lock:
            for k in kw:
                cfg.dynamic_layer.pop(k, None)
    return undo


class _Spy:
    """`runtime.build_traverse_fn` with every build's keywords and, of
    each program run, its inputs and the capture's keys noted."""

    def __init__(self):
        self.builds, self.runs = [], []
        self.real = hop.build_traverse_fn

    def __call__(self, *a, **kw):
        fn = self.real(*a, **kw)
        self.builds.append(kw)

        def run(blocks, frontier):
            res = fn(blocks, frontier)
            self.runs.append((kw, jax.device_get(blocks),
                              set(res.get("cap", ()))))
            return res
        run.noted = fn.noted
        return run


@pytest.fixture()
def spy(monkeypatch):
    s = _Spy()
    monkeypatch.setattr(runtime, "build_traverse_fn", s)
    return s


@functools.lru_cache(maxsize=None)
def _plane(parts, hubs, pred):
    """The kernel inputs of a GO that reads no rank over an unarmed
    snapshot, as the runtime hands them to its program: (blocks on the
    host, builder keywords, P)."""
    spy = _Spy()
    undo = _flags(tpu_delta_max_edges=0,
                  **({"tpu_degree_split_threshold": 8} if hubs else {}))
    runtime.build_traverse_fn = spy
    try:
        eng = QueryEngine(_store(parts, hubs),
                          tpu_runtime=TpuRuntime(make_mesh(1)))
        _rows(eng, "GO 2 STEPS FROM 1, 2, 3, 4, 5, 6, 8, 9 OVER knows "
              + ("WHERE knows.w > 10 " if pred else "") + YIELD)
    finally:
        runtime.build_traverse_fn = spy.real
        undo()
    kw, blocks, cap = spy.runs[-1]
    kw = {k: v for k, v in kw.items() if k != "lanes"}
    # the rule left this statement's program without rank
    assert kw["carry_rank"] is False and "rank" not in cap
    assert kw["yield_cols"] == ("f", "w") and "d_src" not in blocks[0]
    assert bool(kw["hub_dense"] is not None and len(kw["hub_dense"])) == hubs
    assert bool(kw["pred_cols"]) == pred
    return blocks, kw, blocks[0]["indptr"].shape[0]


def _frontier(blocks, kw, P, share, seed=0):
    hubs = 0 if kw.get("hub_dense") is None else len(kw["hub_dense"])
    vmax = blocks[0]["indptr"].shape[1] - 1 - hubs
    return np.random.default_rng(seed).random((P, vmax)) < share


# -- (a) parity: the program without rank against the program with it ---------


@pytest.mark.parametrize("hubs", [False, True], ids=["no-hubs", "degree-split"])
@pytest.mark.parametrize("pred", [False, True], ids=["no-pred", "w-over-c"])
@pytest.mark.parametrize("parts", [1, 8], ids=["1-part", "8-parts"])
@pytest.mark.parametrize("plan_chunk", [PC, WHOLE], ids=["members", "whole"])
def test_rank_free_program_returns_what_the_other_returns(
        plan_chunk, parts, pred, hubs):
    blocks, kw, P = _plane(parts, hubs, pred)
    assert P == parts
    f = _frontier(blocks, kw, P, 0.08)

    def run(carry):
        fn = hop.build_traverse_fn(
            None, P, EBS, 2, len(blocks), plan_chunk=plan_chunk,
            **{**kw, "carry_rank": carry})
        return jax.device_get(fn(blocks, f)), fn.noted["slot_gathers"]

    (got, n_got), (want, n_want) = run(False), run(True)
    assert got["hop_edges"].sum() > 0 and got["kcount"].sum() > 0
    for k in SAME:
        assert np.array_equal(got[k], want[k]), k
    assert set(got["cap"]) == set(want["cap"]) - {"rank"}
    assert "rank" in want["cap"] and "rank" not in got["cap"]
    for k, g in got["cap"].items():
        w = want["cap"][k]
        assert g.dtype == w.dtype and g.shape == w.shape, k
        if k.startswith("prop:"):
            _halves_same(g, w, got["kcount"], k)
        else:
            assert np.array_equal(g, w), k
    # a slot of the last hop: nbr and the row offsets, the compact-row
    # table on the whole-bitmap plan, the predicate's column, the hub
    # ids; and the rank, for the program that carries it
    assert n_got == 2 + (plan_chunk == WHOLE) + pred + hubs
    assert n_want == n_got + 1


# -- (b) the one row-offset table against a numpy oracle ----------------------


def _csr(rng, vmax, emax_deg):
    deg = rng.integers(0, emax_deg, vmax)
    indptr = np.zeros(vmax + 1, np.int32)
    indptr[1:] = np.cumsum(deg)
    E = int(indptr[-1]) + 7
    return (indptr, rng.integers(0, vmax, E).astype(np.int32),
            rng.integers(0, 3, E).astype(np.int32))


def _oracle(indptr, f, EB):
    """`eidx[j] = indptr[row] + (j - starts[row])` slot by slot, rows
    ascending, cut at EB."""
    deg = np.where(f, np.diff(indptr), 0)
    starts = np.cumsum(deg) - deg
    rows = np.repeat(np.arange(f.size), deg)[:EB]
    j = np.arange(rows.size)
    return rows, indptr[rows] + (j - starts[rows]), int(deg.sum())


def _slots_by_windows(indptr, nbr, rank, f, EB, P, pid, size=96):
    """What a by-need level body (algo/frontier.py `_level_marks`) asks
    of the expansion: the plan once, then `_expand_slots` a window at a
    time (windows of 96 slots, which tile neither budget here: the last
    is short), put side by side -> (src, dst, rk, eidx, ve, total, ovf)."""
    import jax.numpy as jnp
    total, ovf, plan, _, _ = hop._expand_plan(
        lambda fn: fn, {"indptr": indptr}, pid, f, EB, hop.PLAN_CHUNK)
    cols = [hop._expand_slots(nbr, rank, plan, total, lo, min(size, EB - lo),
                              EB, P, pid) for lo in range(0, EB, size)]
    return tuple(jnp.concatenate(c) for c in zip(*cols)) + (total, ovf)


@pytest.mark.parametrize("plan_chunk", [32, WHOLE], ids=["members", "whole"])
@pytest.mark.parametrize("case", ["empty", "one", "sparse", "dense",
                                  "overflow", "exact"])
def test_eidx_from_the_row_offsets_is_the_oracles(monkeypatch, case,
                                                  plan_chunk):
    monkeypatch.setattr(hop, "PLAN_CHUNK", plan_chunk)
    rng = np.random.default_rng(11)
    vmax, EB, P, pid = 200, 256, 3, 2
    indptr, nbr, rank = _csr(rng, vmax, 9)
    f = np.zeros(vmax, bool)
    if case == "one":
        f[np.flatnonzero(np.diff(indptr))[5]] = True
    elif case == "sparse":
        f = rng.random(vmax) < 0.1
    elif case == "dense":
        f = rng.random(vmax) < 0.3
    elif case == "overflow":
        f[:] = True
    elif case == "exact":       # fills the budget to its last slot
        f[:40] = True
        EB = int(indptr[40])
    rows, want, total = _oracle(indptr, f, EB)
    src, dst, rk, eidx, ve, tot, ovf = jax.device_get(jax.jit(
        lambda ip, nb, r, fb: _slots_by_windows(ip, nb, r, fb, EB, P, pid))(
        indptr, nbr, rank, f))
    n = min(total, EB)
    assert int(tot) == total and bool(ovf) == (total > EB)
    assert ve[:n].all() and not ve[n:].any()
    assert np.array_equal(eidx[:n], want) and not eidx[n:].any()
    assert np.array_equal(src[:n], rows * P + pid) and (src[n:] == -1).all()
    assert np.array_equal(dst[:n], nbr[want]) and (dst[n:] == -1).all()
    assert np.array_equal(rk[:n], rank[want]) and not rk[n:].any()
    if case == "overflow":
        assert total > EB
    if case == "empty":
        assert total == 0
    if case == "exact":
        assert total == EB > 100 and not ovf
    # the level body itself (algo/frontier.py): the far ends of exactly
    # those slots marked, in trips of 64 where they tile the budget
    from nebula_tpu.algo import frontier
    marks, edges, over, trips, budget = jax.device_get(jax.jit(
        lambda ip, nb, r, fb: frontier._level_marks(
            lambda fn: fn, [{"indptr": ip, "nbr": nb, "rank": r, "props": {}}],
            pid, fb, EB, P, vmax, None, (), None, 64))(indptr, nbr, rank, f))
    far = np.zeros(P * vmax, bool)
    far[(nbr[want] % P) * vmax + nbr[want] // P] = True
    assert np.array_equal(marks, far)
    assert int(edges) == total and bool(over) == (total > EB)
    looped = EB % 64 == 0
    assert (int(trips), int(budget)) == (
        (-(-n // 64), EB // 64) if looped else (0, 0))
    assert looped == (case != "exact")


def test_row_offsets_broadcast_a_shards_csr_under_its_lanes():
    """`indptr` may lack the plan's leading axes (one shard's CSR under
    several lanes): the subtraction broadcasts."""
    rng = np.random.default_rng(3)
    indptr, _, _ = _csr(rng, 50, 5)
    starts = rng.integers(0, 100, (4, 50)).astype(np.int32)
    off = np.asarray(hop._row_offsets(indptr, starts))
    assert off.shape == (4, 50)
    assert np.array_equal(off, indptr[None, :-1] - starts)


# -- (c) the rule: whoever reads rank still gets it ---------------------------


GO = "GO 2 STEPS FROM 1, 2, 3, 4, 5, 6, 8, 9 OVER knows "


def _gathers():
    s = stats().snapshot()
    return (s.get("tpu_hop_slot_gathers.sum", 0.0),
            s.get("tpu_hop_slot_gathers.count", 0))


def _check(spy, st, q, carry, gathers, rt=None):
    """`q` on the device returns the host engine's rows; the program it
    ran was built with `carry` and observed `gathers` a slot."""
    rt = rt or TpuRuntime(make_mesh(1))
    s0, c0 = _gathers()
    n0 = len(spy.runs)
    assert _rows(QueryEngine(st, tpu_runtime=rt), q) == \
        _rows(QueryEngine(st), q)
    assert len(spy.runs) > n0, "the statement did not reach the device"
    kw, _, cap = spy.runs[-1]
    assert kw["carry_rank"] is carry and ("rank" in cap) is carry
    s1, c1 = _gathers()
    assert c1 > c0 and (s1 - s0) / (c1 - c0) == gathers
    return rt


@pytest.mark.parametrize("q,carry,gathers", [
    # nothing reads rank: nbr, off, and vid_of on these narrow bitmaps
    (GO + YIELD, False, 3),
    (GO + "WHERE knows.w > 10 " + YIELD, False, 4),
    (GO + "YIELD dst(edge) AS d, rank(edge) AS r", True, 4),
    (GO + "YIELD dst(edge) AS d, knows._rank AS r", True, 4),
    (GO + "WHERE rank(edge) == 1 " + YIELD, True, 4),
    # a MATCH frame is edge identities
    ("MATCH (a:person)-[e:knows*1..2]->(b) WHERE id(a) == 7 "
     "RETURN count(*)", True, 4),
], ids=["reads-none", "reads-none-w-pred", "yield-rank", "yield-_rank",
        "rank-predicate", "match-frames"])
def test_the_rule_over_an_unarmed_snapshot(spy, q, carry, gathers):
    undo = _flags(tpu_delta_max_edges=0)
    try:
        _check(spy, store_p(2, n=200), q, carry, gathers)
    finally:
        undo()


def test_triples_without_yields_carry_rank(spy):
    """`TpuRuntime.traverse(yields=None)` fetches everything: the Edge
    objects it builds hold their ranks."""
    undo = _flags(tpu_delta_max_edges=0)
    try:
        st = store_p(2, n=200)
        rows, _ = TpuRuntime(make_mesh(1)).traverse(
            st, "g", [1, 2, 3, 4], ["knows"], "out", 2)
    finally:
        undo()
    kw, _, cap = spy.runs[-1]
    assert kw["carry_rank"] is True and "rank" in cap
    got = sorted(repr([e.src, e.dst, e.ranking]) for _, e, _ in rows)
    assert got and got == _rows(
        QueryEngine(st), "GO 2 STEPS FROM 1, 2, 3, 4 OVER knows "
        "YIELD src(edge), dst(edge), rank(edge)")


def test_an_armed_plane_keeps_rank_empty_or_not(spy):
    """Default flags arm the delta plane: the program of a statement
    that reads no rank carries it all the same, so the plane's first
    row (which puts rank into the fetch, for the host's re-sort) finds
    its program compiled."""
    st = store_p(2, n=200)
    q = GO + YIELD
    rt = _check(spy, st, q, True, 4)                 # armed, empty
    builds, pins = len(spy.builds), stats().snapshot().get("tpu_pins", 0)
    st.insert_edge("g", 1, "knows", 41, 0, {"w": 60, "f": 0.5, "tag": "ann"})
    _check(spy, st, q, True, 4, rt)                  # holding a row
    assert stats().snapshot().get("tpu_pins", 0) == pins
    assert len(spy.builds) == builds, "the row needed another program"
    assert spy.runs[-1][1][0]["d_valid"].any()


def test_statements_that_differ_in_rank_share_no_program(spy):
    undo = _flags(tpu_delta_max_edges=0)
    try:
        st = store_p(2, n=200)
        rt = _check(spy, st, GO + "YIELD dst(edge) AS d", False, 3)
        _check(spy, st, GO + "YIELD dst(edge) AS d, rank(edge) AS r",
               True, 4, rt)
        _check(spy, st, GO + "YIELD dst(edge) AS d", False, 3, rt)
    finally:
        undo()
    assert [kw["carry_rank"] for kw in spy.builds] == [False, True]


# -- (d) structure: the rank-free program reads no rank leaf ------------------


def _leaf_used(closed, blocks, f, leaf):
    """Whether the traced program's input `blocks[i][leaf]` feeds any
    equation, per block."""
    (eqn,) = closed.jaxpr.eqns          # the jitted program
    inner = eqn.params["jaxpr"].jaxpr
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path((blocks, f))[0]]
    assert len(paths) == len(inner.invars)
    read = {id(v) for e in inner.eqns for v in e.invars}
    read |= {id(v) for v in inner.outvars}
    return [id(v) in read for p, v in zip(paths, inner.invars)
            if p.endswith(f"['{leaf}']")]


@pytest.mark.parametrize("plan_chunk", [PC, WHOLE], ids=["members", "whole"])
@pytest.mark.parametrize("lanes", [False, True], ids=["solo", "lanes"])
def test_the_rank_leaf_is_an_unused_input(lanes, plan_chunk):
    blocks, kw, P = _plane(2, False, True)
    f = _frontier(blocks, kw, P, 0.08)
    if lanes:
        f = np.stack([f, _frontier(blocks, kw, P, 0.05, seed=1)])

    def used(carry, leaf):
        fn = hop.build_traverse_fn(
            None, P, EBS, 2, len(blocks), lanes=lanes,
            plan_chunk=plan_chunk, **{**kw, "carry_rank": carry})
        return _leaf_used(jax.make_jaxpr(fn)(blocks, f), blocks, f, leaf)

    assert used(False, "rank") == [False] * len(blocks)
    assert used(True, "rank") == [True] * len(blocks)
    assert used(False, "nbr") == [True] * len(blocks)
