"""`TpuRuntime.bfs` against a numpy BFS written here (PR 41): the level of
every vertex over a `knows_symmetric` snapshot pinned with `pin_prebuilt`,
for 1 to 5 levels, with and without the reverse blocks, on a graph that
takes a level bottom-up and on one that takes none; the `bottom_up` flags
the program returns against a host replay of its switch rule, `hop_edges`
by the direction each level took, the seven `tpu_bfs_*` counters by what
the flags and `hop_edges` say, the `tpu:launch` span's attributes, and the
sharded builder's flags on a four-device virtual mesh: all false where
every budget is one trip, the rule by trips where the levels loop (PR 45;
test_bfs_mesh_direction.py has the rule's own cases).

Since PR 42 a level body runs by need (algo/frontier.py `_level_marks`):
the same levels and counts over budgets of one trip, several trips, a
width trips do not tile and a budget the level overflows, the trips each
level ran against the fullest part of the host replay, the slots RUN that
`tpu_bfs_budget_slots` now counts, and a looped program that holds no
gather as wide as its budget."""
from __future__ import annotations

import contextlib
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import loader  # noqa: E402

SPACE = "snb"
SCHEMA = {"KNOWS": {"w": "int", "f": "double", "city": "string"}}
# a part's rows fit the first edge budget (2,048 slots) in either direction of
# either graph: one program a case, no ladder.  `dense` reaches an eighth of
# the unvisited by its third level; `sparse` reaches a few dozen in five
GRAPHS = {"dense": {"persons": 1600, "degree": 8, "max_degree": 64},
          "sparse": {"persons": 1600, "degree": 1.5, "max_degree": 3}}
COUNTERS = ("tpu_bfs_runs", "tpu_bfs_levels", "tpu_bfs_levels_bottom_up", "tpu_bfs_edges",
            "tpu_bfs_budget_slots", "tpu_bfs_chunks_run", "tpu_bfs_chunks_budget")
# a traverse program's engagement counters, which no BFS moves
HOP_CHUNKS = ("tpu_hop_chunks_run", "tpu_hop_chunks_budget")


def numpy_bfs(n, src, dst, start, max_steps):
    """-> (level of every vertex, the frontier entering each level)."""
    level = np.full(n, -1, np.int64)
    level[start] = 0
    frontier, entered = np.asarray([start]), []
    for depth in range(1, max_steps + 1):
        entered.append(frontier)
        reached = np.unique(dst[np.isin(src, frontier)])
        frontier = reached[level[reached] < 0]
        level[frontier] = depth
    return level, entered


def replay(n, src, level, entered, have_rev, parts=None):
    """The program's switch rule and what each level then expands, on the
    host: a level goes bottom-up when eight times its frontier outnumbers
    the unvisited (bfs.py), and then expands the in-edges of every
    unvisited vertex where a top-down level expands the frontier's
    out-edges (a symmetric graph: a vertex's in-edges are as many as its
    out-edges).  With `parts`, also what each part expands a level (a
    vertex is its part's, `vid % parts`): (parts,) a level."""
    deg = np.bincount(src, minlength=n)
    flags, edges, per_part = [], [], []
    for depth, frontier in enumerate(entered, start=1):
        unvisited = (level < 0) | (level >= depth)
        bottom_up = bool(have_rev and frontier.size * 8 > unvisited.sum())
        flags.append(bottom_up)
        who = np.flatnonzero(unvisited) if bottom_up else frontier
        edges.append(int(deg[who].sum()))
        if parts:
            per_part.append(np.bincount(who % parts, weights=deg[who],
                                        minlength=parts).astype(np.int64))
    return (flags, edges, per_part) if parts else (flags, edges)


def mesh_replay(n, src, dst, level, entered, parts, e_cap, chunk, have_rev=True):
    """The sharded program's rule on the host: a level whose budget loops
    goes bottom-up where the in-edges of the unvisited take fewer trips on
    their fullest part than the frontier's out-edges on theirs, a
    bottom-up trip weighed by the module's constant; a tie, and a budget
    of one trip, stay top-down.  A vertex is its part's (`vid % parts`).
    -> (flags, slots a level expands, (parts,) slots a level and part)."""
    from nebula_tpu.tpu.bfs import BOTTOM_UP_TRIP_COST
    out_deg, in_deg = np.bincount(src, minlength=n), np.bincount(dst, minlength=n)
    flags, edges, per_part = [], [], []
    for depth, (frontier, eb) in enumerate(zip(entered, e_cap), start=1):
        unvisited = np.flatnonzero((level < 0) | (level >= depth))
        td = np.bincount(frontier % parts, weights=out_deg[frontier],
                         minlength=parts).astype(np.int64)
        bu = np.bincount(unvisited % parts, weights=in_deg[unvisited],
                         minlength=parts).astype(np.int64)
        trips = [max(-(-int(min(x, eb)) // chunk) for x in pp) for pp in (td, bu)]
        up = bool(have_rev and eb > chunk and not eb % chunk
                  and trips[1] * BOTTOM_UP_TRIP_COST < trips[0])
        flags.append(up)
        per_part.append(bu if up else td)
        edges.append(int(per_part[-1].sum()))
    return flags, edges, per_part


@pytest.fixture(scope="module")
def pinned():
    """{(graph, have_rev): (runtime, store, n, src, dst, a start)}: each
    graph pinned on one device with both directions of KNOWS, and with
    the out-block alone."""
    from nebula_tpu.tpu.runtime import TpuRuntime
    gen = loader.module("reference/generators", "knows_symmetric")
    mesh = loader.module("builders", "prebuilt_mesh")
    plain = loader.module("builders", "prebuilt_snapshot")
    out, rts = {}, []
    for name, sizes in GRAPHS.items():
        tables = gen.generate(sizes, 2 ** 31 + 41)
        e = tables["edges"]["KNOWS"]
        start = int(np.argmax(np.bincount(e["src"], minlength=tables["n"])))
        for have_rev in (True, False):
            snap = mesh.snapshot_from_pairs(tables, SCHEMA, 8, SPACE)
            if not have_rev:
                del snap.blocks[("KNOWS", "in")]
            rt = TpuRuntime(n_devices=1)
            rt.pin_prebuilt(snap)
            rts.append(rt)
            out[name, have_rev] = (rt, plain.SnapshotStore(snap), tables["n"], e["src"],
                                   e["dst"], start)
    yield out
    for rt in rts:
        rt.unpin(SPACE)


def _moved(run):
    from nebula_tpu.utils.stats import stats
    c0 = stats().snapshot()
    got = run()
    c1 = stats().snapshot()
    return got, {k: c1.get(k, 0) - c0.get(k, 0)
                 for k in COUNTERS + HOP_CHUNKS + ("tpu_kernel_runs",)}


@contextlib.contextmanager
def trips_of(chunk, *runtimes):
    """Every BFS program built inside takes trips of `chunk` slots; the
    runtimes forget the programs they built, going in and coming out."""
    from nebula_tpu.tpu import bfs as bfs_mod
    real = {k: getattr(bfs_mod, k) for k in ("build_bfs_fn_local", "build_bfs_fn")}
    for rt in runtimes:
        rt._fns.clear()
    for k, fn in real.items():
        setattr(bfs_mod, k, lambda *a, _fn=fn, **kw: _fn(*a, chunk=chunk, **kw))
    try:
        yield
    finally:
        for k, fn in real.items():
            setattr(bfs_mod, k, fn)
        for rt in runtimes:
            rt._fns.clear()


@pytest.mark.parametrize("max_steps", [1, 2, 3, 4, 5])
@pytest.mark.parametrize("have_rev", [True, False], ids=["rev", "no-rev"])
@pytest.mark.parametrize("graph", sorted(GRAPHS))
def test_levels_flags_and_counters(pinned, graph, have_rev, max_steps):
    rt, store, n, src, dst, start = pinned[graph, have_rev]
    P = 8
    (dist, st), moved = _moved(
        lambda: rt.bfs(store, SPACE, [start], ["KNOWS"], "out", max_steps))
    vid = np.arange(n)
    want, entered = numpy_bfs(n, src, dst, start, max_steps)
    assert np.array_equal(np.asarray(dist)[vid % P, vid // P], want)
    flags, edges = replay(n, src, want, entered, have_rev)
    assert st.bottom_up == flags and len(flags) == max_steps
    assert st.hop_edges == edges
    if graph == "sparse" or not have_rev:
        assert not any(flags)
    elif max_steps >= 3:
        assert any(flags)                                 # the branch is taken
    assert st.retries == 0 and all(e == rt.init_eb for e in st.e_cap)
    # a budget of 2,048 slots fits one trip of the module's constant: the
    # straight-line program, which runs its budgets whole and no trip
    assert moved == {"tpu_bfs_runs": 1, "tpu_bfs_levels": max_steps,
                     "tpu_bfs_levels_bottom_up": sum(flags), "tpu_bfs_edges": sum(edges),
                     "tpu_bfs_budget_slots": P * sum(st.e_cap), "tpu_kernel_runs": 1,
                     "tpu_bfs_chunks_run": 0, "tpu_bfs_chunks_budget": 0,
                     "tpu_hop_chunks_run": 0, "tpu_hop_chunks_budget": 0}
    assert st.chunks_run == st.chunks_budget == 0


TRIPS = {"one-trip": 2048, "several": 64, "untiled": 96}


@pytest.mark.parametrize("trip", sorted(TRIPS))
@pytest.mark.parametrize("have_rev", [True, False], ids=["rev", "no-rev"])
@pytest.mark.parametrize("graph", sorted(GRAPHS))
def test_levels_by_need_over_budgets_of_one_trip_several_and_untiled(pinned, graph, have_rev,
                                                                     trip):
    """The budget stays 2,048 slots a level and the trip varies: a budget
    of one trip, or one that trips do not tile, is the straight-line
    program (no trip counted, whole budgets run); at 32 trips a budget a
    level runs the trips its fullest part fills."""
    rt, store, n, src, dst, start = pinned[graph, have_rev]
    P, chunk = 8, TRIPS[trip]
    with trips_of(chunk, rt):
        (dist, st), moved = _moved(
            lambda: rt.bfs(store, SPACE, [start], ["KNOWS"], "out", 5))
    vid = np.arange(n)
    want, entered = numpy_bfs(n, src, dst, start, 5)
    assert np.array_equal(np.asarray(dist)[vid % P, vid // P], want)
    flags, edges, per_part = replay(n, src, want, entered, have_rev, P)
    assert (st.bottom_up, st.hop_edges, st.retries) == (flags, edges, 0)
    assert moved["tpu_bfs_edges"] == sum(edges)
    assert not moved["tpu_hop_chunks_run"] and not moved["tpu_hop_chunks_budget"]
    if trip != "several":
        assert st.chunks_run == st.chunks_budget == 0
        assert moved["tpu_bfs_chunks_run"] == moved["tpu_bfs_chunks_budget"] == 0
        assert moved["tpu_bfs_budget_slots"] == P * sum(st.e_cap)
        return
    # one chip: a vmapped loop runs every part to the fullest part's trips
    trips = [-(-int(min(pp.max(), e)) // chunk) for pp, e in zip(per_part, st.e_cap)]
    assert st.chunks_run == moved["tpu_bfs_chunks_run"] == P * sum(trips)
    assert st.chunks_budget == moved["tpu_bfs_chunks_budget"] == \
        P * sum(e // chunk for e in st.e_cap)
    assert 0 < st.chunks_run < st.chunks_budget       # no level fills 2,048 slots a part
    assert moved["tpu_bfs_budget_slots"] == st.chunks_run * chunk
    assert sum(edges) <= moved["tpu_bfs_budget_slots"] < P * sum(st.e_cap)


@pytest.mark.parametrize("chunk", [1 << 14, 64], ids=["straight-line", "looped"])
def test_a_ladder_settles_the_counters_once_at_the_converged_budgets(pinned, chunk):
    """A first edge budget under what the dense levels expand: the ladder
    climbs, and the counters move once, by the converged launch.  The
    looped program's overflowed rungs report the counts the straight-line
    program's report, so both stop at the same budgets."""
    rt, store, n, src, dst, start = pinned["dense", True]
    was, rt.init_eb = rt.init_eb, 256
    rt._buckets.clear()
    try:
        with trips_of(chunk, rt):
            (dist, st), moved = _moved(
                lambda: rt.bfs(store, SPACE, [start + 1], ["KNOWS"], "out", 4))
    finally:
        rt.init_eb = was
        rt._buckets.clear()
    assert st.retries >= 1 and max(st.e_cap) > 256
    want, entered = numpy_bfs(n, src, dst, start + 1, 4)
    vid = np.arange(n)
    assert np.array_equal(np.asarray(dist)[vid % 8, vid // 8], want)
    flags, edges, per_part = replay(n, src, want, entered, True, 8)
    assert (st.bottom_up, st.hop_edges) == (flags, edges)
    # the budgets the whole-budget level bodies of PR 41's tree stop at
    assert (st.e_cap, st.retries) == ([256, 256, 512, 1024], 1)
    assert moved["tpu_bfs_runs"] == moved["tpu_kernel_runs"] == 1
    assert moved["tpu_bfs_edges"] == sum(edges)
    if chunk == 64:
        trips = [-(-int(pp.max()) // 64) for pp in per_part]
        assert moved["tpu_bfs_chunks_run"] == 8 * sum(trips)
        assert moved["tpu_bfs_chunks_budget"] == 8 * sum(e // 64 for e in st.e_cap)
        assert moved["tpu_bfs_budget_slots"] == 8 * sum(trips) * 64 < 8 * sum(st.e_cap)
    else:
        assert moved["tpu_bfs_budget_slots"] == 8 * sum(st.e_cap)
        assert moved["tpu_bfs_chunks_run"] == moved["tpu_bfs_chunks_budget"] == 0


def test_a_traverse_moves_no_bfs_counter_and_a_bfs_launch_span_says_what_it_did(pinned):
    from nebula_tpu.utils import trace
    rt, store, n, src, dst, start = pinned["dense", True]
    (rows, st), moved = _moved(
        lambda: rt.traverse(store, SPACE, [start], ["KNOWS"], "out", 2))
    assert moved["tpu_kernel_runs"] == 1 and st.bottom_up == []
    assert not any(moved[k] for k in COUNTERS)
    with trace.start_trace("query:test") as root:
        _, st = rt.bfs(store, SPACE, [start], ["KNOWS"], "out", 5)
    spans = trace.trace_store().get(root.trace_id)["spans"]
    launch, = [s for s in spans if s["name"] == "tpu:launch"]
    assert launch["attrs"] == {"kernel": "bfs", "levels": 5, "eb": list(st.e_cap),
                               "bottom_up": sum(st.bottom_up), "chunks_run": 0,
                               "chunks_budget": 0}
    assert sum(st.bottom_up) >= 1


def test_a_looped_bfs_moves_no_traverse_counter_and_its_span_says_its_trips(pinned):
    """The mirror: a BFS whose levels loop settles its trips under
    `tpu_bfs_chunks_*` and leaves `tpu_hop_chunks_*`, which
    `kernel.chunk_share` reads in a cell that runs both, where they were."""
    from nebula_tpu.utils import trace
    rt, store, n, src, dst, start = pinned["dense", True]
    with trips_of(64, rt), trace.start_trace("query:test") as root:
        (_, st), moved = _moved(lambda: rt.bfs(store, SPACE, [start], ["KNOWS"], "out", 5))
    assert 0 < moved["tpu_bfs_chunks_run"] < moved["tpu_bfs_chunks_budget"]
    assert not any(moved[k] for k in HOP_CHUNKS)
    spans = trace.trace_store().get(root.trace_id)["spans"]
    launch, = [s for s in spans if s["name"] == "tpu:launch"]
    assert launch["attrs"]["chunks_run"] == st.chunks_run == moved["tpu_bfs_chunks_run"]
    assert launch["attrs"]["chunks_budget"] == st.chunks_budget == 8 * 5 * 2048 // 64


@pytest.mark.parametrize("max_steps", [2, 5])
def test_the_sharded_builder_says_every_level_went_top_down(max_steps):
    """Four virtual devices, one part each, as tests/unit/test_sharded.py
    stands its mesh up: the same levels, every flag false (a budget of
    2,048 slots is one trip of the module's constant: no level has a
    choice)."""
    from nebula_tpu.tpu import TpuRuntime, make_mesh
    gen = loader.module("reference/generators", "knows_symmetric")
    mesh = loader.module("builders", "prebuilt_mesh")
    plain = loader.module("builders", "prebuilt_snapshot")
    tables = gen.generate(GRAPHS["dense"], 2 ** 31 + 41)
    e = tables["edges"]["KNOWS"]
    n, P = tables["n"], 4
    snap = mesh.snapshot_from_pairs(tables, SCHEMA, P, SPACE)
    rt = TpuRuntime(make_mesh(P))
    assert not rt.local_mode
    rt.pin_prebuilt(snap)
    try:
        (dist, st), moved = _moved(lambda: rt.bfs(
            plain.SnapshotStore(snap), SPACE, [7], ["KNOWS"], "out", max_steps))
    finally:
        rt.unpin(SPACE)
    want, entered = numpy_bfs(n, e["src"], e["dst"], 7, max_steps)
    vid = np.arange(n)
    assert np.array_equal(np.asarray(dist)[vid % P, vid // P], want)
    assert st.bottom_up == [False] * max_steps
    assert st.hop_edges == replay(n, e["src"], want, entered, False)[1]
    assert moved["tpu_bfs_levels_bottom_up"] == 0 and moved["tpu_bfs_levels"] == max_steps
    assert moved["tpu_bfs_budget_slots"] == P * sum(st.e_cap)
    assert moved["tpu_bfs_chunks_run"] == moved["tpu_bfs_chunks_budget"] == 0


# -- the builders themselves: an overflowed budget, and what a looped program holds --


def _capture(rt, run):
    """(builder name, its arguments, the operands of its program's last
    call) of the BFS program `run` makes `rt` build."""
    from nebula_tpu.tpu import bfs as bfs_mod
    real = {k: getattr(bfs_mod, k) for k in ("build_bfs_fn_local", "build_bfs_fn")}
    seen = []

    def spy(name):
        def build(*a, **kw):
            fn = real[name](*a, **kw)

            def call(*operands):
                seen.append((name, a, kw, operands))
                return fn(*operands)
            call.chunk = fn.chunk
            return call
        return build
    rt._fns.clear()
    for k in real:
        setattr(bfs_mod, k, spy(k))
    try:
        run()
    finally:
        for k, fn in real.items():
            setattr(bfs_mod, k, fn)
        rt._fns.clear()
    return seen[-1]


@pytest.fixture(scope="module")
def mesh_of_four():
    """(runtime, store, n, src, dst): the dense graph, a part a device."""
    from nebula_tpu.tpu import TpuRuntime, make_mesh
    gen = loader.module("reference/generators", "knows_symmetric")
    mesh = loader.module("builders", "prebuilt_mesh")
    plain = loader.module("builders", "prebuilt_snapshot")
    tables = gen.generate(GRAPHS["dense"], 2 ** 31 + 41)
    e = tables["edges"]["KNOWS"]
    snap = mesh.snapshot_from_pairs(tables, SCHEMA, 4, SPACE)
    rt = TpuRuntime(make_mesh(4))
    assert not rt.local_mode
    rt.pin_prebuilt(snap)
    yield rt, plain.SnapshotStore(snap), tables["n"], e["src"], e["dst"]
    rt.unpin(SPACE)


@pytest.mark.parametrize("layout", ["one-chip", "mesh"])
def test_a_level_that_overflows_its_budget_fills_its_trips_and_says_so(pinned, mesh_of_four,
                                                                       layout):
    """Each builder at budgets of 256 slots a level, four trips of 64,
    under what the dense levels expand: the first level that overflows
    reports its TRUE size (its frontier was whole), sets `ovf_expand`,
    runs every trip of its budget and marks a strict part of the level's
    vertices (the slots past the budget are left out, as they were by the
    whole-budget body)."""
    from nebula_tpu.tpu import bfs as bfs_mod
    if layout == "one-chip":
        rt, store, n, src, dst, _ = pinned["dense", True]
        P = 8
    else:
        rt, store, n, src, dst = mesh_of_four
        P = 4
    start = 7
    name, a, kw, operands = _capture(
        rt, lambda: rt.bfs(store, SPACE, [start], ["KNOWS"], "out", 4))
    # the arguments before the budgets, and those after them
    at = 2 if name == "build_bfs_fn" else 1
    fn = getattr(bfs_mod, name)(*a[:at], (256,) * 4, *a[at + 1:], **kw, chunk=64)
    import jax
    got = jax.device_get(fn(*operands))
    want, entered = numpy_bfs(n, src, dst, start, 4)
    flags, edges, per_part = replay(n, src, want, entered, layout == "one-chip", P)
    first = next(i for i, pp in enumerate(per_part) if pp.max() > 256)
    assert got["ovf_expand"].any()
    # levels up to the first that overflows saw whole frontiers
    assert got["hop_edges"].sum(axis=0)[:first + 1].tolist() == edges[:first + 1]
    assert got["bottom_up"][:first + 1].tolist() == flags[:first + 1]
    assert (got["chunks_budget"] == 256 // 64).all()
    full = per_part[first] >= 256
    assert (got["chunks_run"][:, first] == 4).all() if layout == "one-chip" else \
        (got["chunks_run"][full, first] == 4).all()
    # level 1 expands one start: one trip of four (on one chip every part
    # runs the fullest part's; on the mesh the start's own shard alone)
    assert got["chunks_run"][:, 0].tolist() == (
        [1] * P if layout == "one-chip" else [int(p == start % P) for p in range(P)])
    vid = np.arange(n)
    dist = got["dist"][vid % P, vid // P]
    assert np.array_equal(dist[want <= first], want[want <= first])
    reached, level = dist == first + 1, want == first + 1
    assert reached.sum() < level.sum() and not (reached & ~level).any()


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold
    (`pjit`, `while`, `cond`, `shard_map`, ...)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


@pytest.mark.parametrize("layout", ["one-chip", "mesh"])
def test_a_looped_program_holds_no_gather_as_wide_as_its_budget(pinned, mesh_of_four, layout):
    """Shapes, not time: with a budget of 4,096 slots and trips of 64 no
    `gather` of the traced program has an output with the budget among its
    dimensions (`nbr[eidx]`, the row offsets, the membership test all take
    a trip's 64 indices a part); the straight-line program of the same
    budget, beside it, has them."""
    import jax
    from nebula_tpu.tpu import bfs as bfs_mod
    if layout == "one-chip":
        rt, store, *_ = pinned["dense", True]
    else:
        rt, store, *_ = mesh_of_four
    name, a, kw, operands = _capture(
        rt, lambda: rt.bfs(store, SPACE, [7], ["KNOWS"], "out", 3))
    at = 2 if name == "build_bfs_fn" else 1
    EB = 4096
    assert all(EB not in x.shape for x in jax.tree.leaves(operands))

    def widest(chunk):
        fn = getattr(bfs_mod, name)(*a[:at], (EB,) * 3, *a[at + 1:], **kw, chunk=chunk)
        gathers = [e for e in _eqns(jax.make_jaxpr(fn)(*operands).jaxpr)
                   if e.primitive.name == "gather"]
        assert gathers
        return [e for e in gathers if any(EB in v.aval.shape for v in e.outvars)]
    assert not widest(64)
    assert widest(EB)


@pytest.mark.parametrize("start", [7, 1203])
def test_each_shard_runs_its_own_trips(mesh_of_four, start):
    """Four shards, trips of 64: the levels are the oracle's and a level's
    trips are the sum over the shards of what each shard's own expansion
    fills (no shard waits for the fullest one), in the direction the
    shards agreed on (PR 45: `mesh_replay`, the rule by trips)."""
    rt, store, n, src, dst = mesh_of_four
    P, chunk = 4, 64
    with trips_of(chunk, rt):
        (dist, st), moved = _moved(
            lambda: rt.bfs(store, SPACE, [start], ["KNOWS"], "out", 5))
    want, entered = numpy_bfs(n, src, dst, start, 5)
    vid = np.arange(n)
    assert np.array_equal(np.asarray(dist)[vid % P, vid // P], want)
    flags, edges, per_part = mesh_replay(n, src, dst, want, entered, P, st.e_cap, chunk)
    assert (st.bottom_up, st.hop_edges) == (flags, edges) and any(flags)
    trips = sum(int(-(-min(x, e) // chunk)) for pp, e in zip(per_part, st.e_cap) for x in pp)
    assert st.chunks_run == moved["tpu_bfs_chunks_run"] == trips
    assert moved["tpu_bfs_budget_slots"] == trips * chunk
    assert moved["tpu_bfs_chunks_budget"] == P * sum(e // chunk for e in st.e_cap)
    assert not any(moved[k] for k in HOP_CHUNKS)
