"""The sharded BFS program chooses each level's direction (PR 45;
tpu/bfs.py `build_bfs_fn`, algo/frontier.py `sharded_level_step`): on
tier-1's virtual devices, with trips of 64 slots so that a level spans
several.

The levels of seeded `knows_symmetric` graphs over 2 and 4 parts against
the one-chip program's and a numpy BFS; `stats.bottom_up`, `hop_edges`
and the trips each shard ran against a host model of the rule (the trips
either direction takes on its FULLEST part, a bottom-up trip weighed by
`BOTTOM_UP_TRIP_COST`, a tie top-down), level for level, on a graph that
takes a level bottom-up and on one that takes none; the gather's bytes
from the shapes; a filtered BFS whose predicate reads both ends, a
degree-split hub and an armed delta plane (empty, and holding a row on
one shard) through the choice; and the programs themselves: a level
whose budget is one trip has no second branch and no `all_gather`."""
from __future__ import annotations

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "tests", "unit")):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchmarks.lib import loader  # noqa: E402
from nebula_tpu.core.value import NULL  # noqa: E402
from nebula_tpu.query.parser import parse  # noqa: E402
from nebula_tpu.tpu import TpuRuntime, make_mesh  # noqa: E402
from nebula_tpu.tpu import bfs as bfs_mod  # noqa: E402
from nebula_tpu.tpu.hop import a2a_payload_bytes  # noqa: E402
from nebula_tpu.utils.config import get_config  # noqa: E402
from nebula_tpu.utils.stats import stats  # noqa: E402

from test_bfs_levels import (GRAPHS, SCHEMA, SPACE, _eqns, mesh_replay,  # noqa: E402
                             numpy_bfs, trips_of)
from test_delta import store_p  # noqa: E402
from test_tpu import _hubby_store  # noqa: E402

TRIP, STEPS = 64, 5
MOVED = ("tpu_bfs_runs", "tpu_bfs_levels", "tpu_bfs_levels_bottom_up", "tpu_bfs_edges",
         "tpu_bfs_chunks_run", "tpu_bfs_chunks_budget", "tpu_bfs_gather_bytes",
         "tpu_bfs_exchange_bytes", "tpu_all_to_all_bytes")


def _moved(run):
    c0 = stats().snapshot()
    got = run()
    c1 = stats().snapshot()
    return got, {k: c1.get(k, 0) - c0.get(k, 0) for k in MOVED}


@pytest.fixture(scope="module")
def meshes():
    """{(graph, parts): (mesh runtime, one-chip runtime, store, tables)},
    pinned as they are asked for."""
    gen = loader.module("reference/generators", "knows_symmetric")
    mesh = loader.module("builders", "prebuilt_mesh")
    plain = loader.module("builders", "prebuilt_snapshot")
    made = {}

    def get(graph, parts, seed):
        key = graph, parts, seed
        if key not in made:
            tables = gen.generate(GRAPHS[graph], seed)
            snap = mesh.snapshot_from_pairs(tables, SCHEMA, parts, SPACE)
            rts = TpuRuntime(make_mesh(parts)), TpuRuntime(n_devices=1)
            assert not rts[0].local_mode and rts[1].local_mode
            for rt in rts:
                rt.pin_prebuilt(snap)
            made[key] = (*rts, plain.SnapshotStore(snap), tables)
        return made[key]
    yield get
    for rt_mesh, rt_one, *_ in made.values():
        rt_mesh.unpin(SPACE)
        rt_one.unpin(SPACE)


@pytest.mark.parametrize("seed", [2 ** 31 + 41, 4502])
@pytest.mark.parametrize("parts", [2, 4])
@pytest.mark.parametrize("graph", sorted(GRAPHS))
def test_levels_flags_and_trips_follow_the_rule(meshes, graph, parts, seed):
    rt, one, store, tables = meshes(graph, parts, seed)
    e = tables["edges"]["KNOWS"]
    n, src, dst = tables["n"], e["src"], e["dst"]
    deg = np.bincount(src, minlength=n)
    vid = np.arange(n)
    took = 0
    with trips_of(TRIP, rt, one):
        for start in (int(np.argmax(deg)), int(np.flatnonzero(deg == deg[deg > 0].min())[0]),
                      seed % n):
            rt.bfs(store, SPACE, [start], ["KNOWS"], "out", STEPS)      # the ladder
            (dist, st), moved = _moved(
                lambda: rt.bfs(store, SPACE, [start], ["KNOWS"], "out", STEPS))
            local, _ = one.bfs(store, SPACE, [start], ["KNOWS"], "out", STEPS)
            want, entered = numpy_bfs(n, src, dst, start, STEPS)
            got = np.asarray(dist)[vid % parts, vid // parts]
            assert np.array_equal(got, want)
            assert np.array_equal(np.asarray(local)[vid % parts, vid // parts], want)
            flags, edges, per_part = mesh_replay(n, src, dst, want, entered, parts, st.e_cap, TRIP)
            assert st.retries == 0 and all(eb % TRIP == 0 for eb in st.e_cap)
            assert st.bottom_up == flags, (start, st.hop_edges, edges)
            assert st.hop_edges == edges
            took += sum(flags)
            # each shard runs its own trips, of the direction the shards agreed on
            trips = sum(-(-int(min(x, eb)) // TRIP) for pp, eb in zip(per_part, st.e_cap)
                        for x in pp)
            assert st.chunks_run == moved["tpu_bfs_chunks_run"] == trips
            assert moved["tpu_bfs_chunks_budget"] == parts * sum(eb // TRIP for eb in st.e_cap)
            assert moved["tpu_bfs_levels_bottom_up"] == sum(flags)
            assert (moved["tpu_bfs_runs"], moved["tpu_bfs_levels"]) == (1, STEPS)
            # the exchange after EVERY level, whatever its direction; the gather
            # before every level that has the choice, in a series of its own
            vmax = rt.snapshots[SPACE].vmax
            assert moved["tpu_bfs_exchange_bytes"] == moved["tpu_all_to_all_bytes"] == \
                st.exchange_bytes == bfs_mod.bfs_exchange_bytes(parts, vmax, STEPS)
            assert moved["tpu_bfs_gather_bytes"] == bfs_mod.bfs_gather_bytes(
                parts, vmax, STEPS) == STEPS * a2a_payload_bytes(parts, vmax) > 0
    # the dense graph takes a level bottom-up from every start, the sparse none
    assert (took >= 3) if graph == "dense" else (took == 0)


def test_one_chip_moves_no_gather_byte(meshes):
    _, one, store, _ = meshes("dense", 2, 4502)
    with trips_of(TRIP, one):
        (_, st), moved = _moved(lambda: one.bfs(store, SPACE, [7], ["KNOWS"], "out", STEPS))
    assert any(st.bottom_up) and moved["tpu_bfs_runs"] == 1
    assert moved["tpu_bfs_gather_bytes"] == moved["tpu_bfs_exchange_bytes"] == 0
    assert bfs_mod.bfs_gather_bytes(1, 125_000, 5) == 0


# -- the programs themselves -------------------------------------------------


def _prims(jaxpr):
    names = [e.primitive.name for e in _eqns(jaxpr)]
    return {k: names.count(k) for k in ("cond", "all_gather", "pmax", "all_to_all")}


@pytest.mark.parametrize("budgets,have_rev,want", [
    # every budget one trip: the parent's program, no branch, no gather
    ((64, 32, 64), True, {"cond": 0, "all_gather": 0, "pmax": 0, "all_to_all": 3}),
    # one collective of each kind a level that has the choice, outside its
    # one `cond`; a width trips do not tile runs straight-line, top-down
    ((64, 256, 1024), True, {"cond": 2, "all_gather": 2, "pmax": 2, "all_to_all": 3}),
    ((64, 96, 1024), True, {"cond": 1, "all_gather": 1, "pmax": 1, "all_to_all": 3}),
    # no reverse blocks, no choice
    ((64, 256, 1024), False, {"cond": 0, "all_gather": 0, "pmax": 0, "all_to_all": 3}),
], ids=["one-trip", "looped", "untiled", "no-rev"])
def test_a_level_has_a_second_branch_only_where_its_budget_loops(budgets, have_rev, want):
    import jax
    P, vmax, E = 4, 500, 4096
    S = jax.ShapeDtypeStruct
    block = {"indptr": S((P, vmax + 1), np.int32), "nbr": S((P, E), np.int32),
             "rank": S((P, E), np.int32), "props": {}}
    if have_rev:
        block.update(rev_indptr=block["indptr"], rev_nbr=block["nbr"],
                     rev_rank=block["rank"], rev_props={})
    fn = bfs_mod.build_bfs_fn(make_mesh(P), P, budgets, len(budgets), vmax,
                              have_rev=have_rev, chunk=TRIP)
    jaxpr = jax.make_jaxpr(fn)((block,), S((P, vmax), np.bool_)).jaxpr
    assert _prims(jaxpr) == want
    assert fn.gather_levels == want["all_gather"] and fn.chunk == TRIP
    # the branches hold the two loops and no collective
    for eqn in _eqns(jaxpr):
        if eqn.primitive.name == "cond":
            inner = [e.primitive.name for br in eqn.params["branches"]
                     for e in _eqns(br.jaxpr)]
            assert "while" in inner
            assert not {"all_gather", "pmax", "psum", "all_to_all"} & set(inner)


# -- a predicate, hubs and an armed plane through the choice ------------------


@pytest.fixture()
def flags():
    cfg = get_config()
    yield cfg
    with cfg.lock:
        for k in ("tpu_delta_max_edges", "tpu_degree_split_threshold"):
            cfg.dynamic_layer.pop(k, None)


def _every_edge(s, d, props):
    return True


def host_levels(st, srcs, steps, keep=_every_edge):
    """{vid: level} by a level-synchronous walk over the store, an edge
    taken where `keep(src, dst, props)`."""
    level = {v: 0 for v in srcs}
    frontier = sorted(level)
    for depth in range(1, steps + 1):
        reached = set()
        for s, _et, _rank, d, props, _sgn in st.get_neighbors("g", frontier, ["knows"], "out"):
            if keep(s, d, props) and d not in level:
                reached.add(d)
        for v in reached:
            level[v] = depth
        frontier = sorted(reached)
    return level


def _bfs_against_the_store(rt, st, n, srcs, steps, cond=None, keep=_every_edge):
    dist, s = rt.bfs(st, "g", srcs, ["knows"], "out", steps, edge_filter=cond)
    want = host_levels(st, srcs, steps, keep)
    got, sd, P = np.asarray(dist), st.space("g"), rt.snapshots["g"].num_parts
    for v in range(n):
        d = sd.dense_id(v)
        assert got[d % P, d // P] == want.get(v, -1), v
    return s, want


@pytest.mark.parametrize("parts", [2, 4])
def test_a_filtered_bfs_goes_bottom_up_with_its_ends_swapped(flags, parts):
    """`$^` is the traversal's source and `$$` its destination in either
    direction: bottom-up expands the reverse adjacency, so the ends the
    predicate sees are swapped back (`_keep`'s `swap_ends`).  The filter
    treats the ends differently, so a level that swapped them wrongly
    reaches other vertices."""
    flags.set_dynamic_many({"tpu_delta_max_edges": 0})
    n, banned_src, banned_dst = 300, (5, 9, 14, 33, 71), 120
    st = store_p(parts, seed=29, n=n, avg_deg=6)
    cond = parse("GO FROM 1 OVER knows WHERE id($^) NOT IN [5, 9, 14, 33, 71] "
                 "AND id($$) != 120 AND knows.w > 5 YIELD dst(edge)").where.filter
    rt = TpuRuntime(make_mesh(parts))
    with trips_of(TRIP, rt):
        s, want = _bfs_against_the_store(
            rt, st, n, [1, 2], STEPS, cond,
            lambda a, b, props: a not in banned_src and b != banned_dst
            and props["w"] is not NULL and props["w"] > 5)
    assert any(s.bottom_up), (s.bottom_up, s.hop_edges, s.e_cap)
    assert banned_dst not in want and len(want) > n // 2
    # a banned source is reached and expands nothing
    assert any(v in want for v in banned_src)


def test_a_degree_split_hub_goes_through_a_sharded_bottom_up_level(flags):
    """The hub's rows live on every part, in both directions: bottom-up, a
    hub row's kept slot marks the HUB, which another part owns, and the
    exchange after the level takes the mark there."""
    flags.set_dynamic_many({"tpu_delta_max_edges": 0, "tpu_degree_split_threshold": 8})
    st = _hubby_store(n=150)
    rt = TpuRuntime(make_mesh(8))
    with trips_of(16, rt):
        s, want = _bfs_against_the_store(rt, st, 150, [1, 2, 3], 4)
    assert len(rt.snapshots["g"].host.hub_dense) > 0
    assert any(s.bottom_up), (s.bottom_up, s.hop_edges, s.e_cap)
    assert want.get(7) is not None          # the hub itself is reached


@pytest.mark.parametrize("parts", [2, 4])
def test_a_plane_that_holds_a_row_on_one_shard_keeps_every_shard_top_down(flags, parts):
    n, srcs = 300, [1, 2]
    st = store_p(parts, seed=29, n=n, avg_deg=6)
    # no plane: the levels' directions to hold the armed runs to
    flags.set_dynamic_many({"tpu_delta_max_edges": 0})
    bare = TpuRuntime(make_mesh(parts))
    with trips_of(TRIP, bare):
        unarmed, _ = _bfs_against_the_store(bare, st, n, srcs, STEPS)
    assert any(unarmed.bottom_up)
    bare.unpin("g")

    flags.set_dynamic_many({"tpu_delta_max_edges": 64})
    rt = TpuRuntime(make_mesh(parts))
    with trips_of(TRIP, rt):
        # armed and empty: no level changes its direction
        armed, _ = _bfs_against_the_store(rt, st, n, srcs, STEPS)
        held = rt.snapshots["g"].delta.host
        assert held.total_edges() == held.total_tombs() == 0
        assert (armed.bottom_up, armed.hop_edges) == (unarmed.bottom_up, unarmed.hop_edges)
        # one row, whose source and destination are one part's
        sd = st.space("g")
        a = next(v for v in range(n) if sd.dense_id(v) % parts == 1)
        b = next(v for v in range(a + 1, n) if sd.dense_id(v) % parts == 1)
        st.insert_edge("g", a, "knows", b, 7, {"w": 60, "f": 0.5, "tag": "ann"})
        rows, want = _bfs_against_the_store(rt, st, n, srcs, STEPS)
    held = rt.snapshots["g"].delta.host
    per_part = held.edges_per_part()
    assert per_part[1] > 0 and sum(per_part) == per_part[1], per_part
    assert rows.bottom_up == [False] * STEPS
    assert a in want and b in want
