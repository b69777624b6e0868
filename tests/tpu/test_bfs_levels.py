"""`TpuRuntime.bfs` against a numpy BFS written here (PR 41): the level of
every vertex over a `knows_symmetric` snapshot pinned with `pin_prebuilt`,
for 1 to 5 levels, with and without the reverse blocks, on a graph that
takes a level bottom-up and on one that takes none; the `bottom_up` flags
the program returns against a host replay of its switch rule, `hop_edges`
by the direction each level took, the five `tpu_bfs_*` counters by what
the flags and `hop_edges` say, the `tpu:launch` span's attributes, and the
sharded builder's all-false flags on a four-device virtual mesh."""
from __future__ import annotations

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
            "tpu_bfs_budget_slots")


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


def replay(n, src, level, entered, have_rev):
    """The program's switch rule and what each level then expands, on the
    host: a level goes bottom-up when eight times its frontier outnumbers
    the unvisited (bfs.py), and then expands the in-edges of every
    unvisited vertex where a top-down level expands the frontier's
    out-edges (a symmetric graph: a vertex's in-edges are as many as its
    out-edges)."""
    deg = np.bincount(src, minlength=n)
    flags, edges = [], []
    for depth, frontier in enumerate(entered, start=1):
        unvisited = (level < 0) | (level >= depth)
        bottom_up = bool(have_rev and frontier.size * 8 > unvisited.sum())
        flags.append(bottom_up)
        edges.append(int(deg[unvisited].sum() if bottom_up else deg[frontier].sum()))
    return flags, edges


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
    return got, {k: c1.get(k, 0) - c0.get(k, 0) for k in COUNTERS + ("tpu_kernel_runs",)}


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
    assert moved == {"tpu_bfs_runs": 1, "tpu_bfs_levels": max_steps,
                     "tpu_bfs_levels_bottom_up": sum(flags), "tpu_bfs_edges": sum(edges),
                     "tpu_bfs_budget_slots": P * sum(st.e_cap), "tpu_kernel_runs": 1}


def test_a_ladder_settles_the_counters_once_at_the_converged_budgets(pinned):
    """A first edge budget under what the dense levels expand: the ladder
    climbs, and the counters move once, by the converged budgets."""
    rt, store, n, src, dst, start = pinned["dense", True]
    was, rt.init_eb = rt.init_eb, 256
    rt._buckets.clear()
    try:
        (dist, st), moved = _moved(
            lambda: rt.bfs(store, SPACE, [start + 1], ["KNOWS"], "out", 4))
    finally:
        rt.init_eb = was
        rt._buckets.clear()
    assert st.retries >= 1 and max(st.e_cap) > 256
    want, entered = numpy_bfs(n, src, dst, start + 1, 4)
    vid = np.arange(n)
    assert np.array_equal(np.asarray(dist)[vid % 8, vid // 8], want)
    flags, edges = replay(n, src, want, entered, True)
    assert (st.bottom_up, st.hop_edges) == (flags, edges)
    assert moved["tpu_bfs_runs"] == moved["tpu_kernel_runs"] == 1
    assert moved["tpu_bfs_budget_slots"] == 8 * sum(st.e_cap)
    assert moved["tpu_bfs_edges"] == sum(edges)


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
                               "bottom_up": sum(st.bottom_up)}
    assert sum(st.bottom_up) >= 1


@pytest.mark.parametrize("max_steps", [2, 5])
def test_the_sharded_builder_says_every_level_went_top_down(max_steps):
    """Four virtual devices, one part each, as tests/unit/test_sharded.py
    stands its mesh up: the same levels, every flag false."""
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
