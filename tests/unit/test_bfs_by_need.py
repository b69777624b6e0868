"""The BFS level bodies by need (algo/frontier.py `_level_marks`, PR 42)
over what a level body can meet besides a plain CSR: an edge predicate
that reads a property column, a degree-split snapshot with hub rows, and
an armed delta plane that holds nothing, rows, or a tombstone; on one
chip and on a mesh of two shards (eight for the hub store, which has
eight parts), on both of which a dense level goes bottom-up.

Every case runs twice through `TpuRuntime.bfs`: with a trip of 64 slots
(an edge budget of 2,048 is 32 trips a level) and with a trip no budget
reaches (the straight-line program).  `dist` is held to a BFS walked here
over `store.get_neighbors`, which sees the writes; `hop_edges`,
`bottom_up` and the converged budgets of the looped program to the
straight-line program's, integer for integer.
"""
import numpy as np
import pytest

from nebula_tpu.core.value import NULL
from nebula_tpu.query.parser import parse
from nebula_tpu.utils.config import get_config

tpu = pytest.importorskip("nebula_tpu.tpu")
from nebula_tpu.tpu import TpuRuntime, make_mesh             # noqa: E402
from nebula_tpu.tpu import bfs as bfs_mod                    # noqa: E402

from test_delta import store_p                               # noqa: E402
from test_tpu import _hubby_store                            # noqa: E402

TRIP = 64
WHOLE = 1 << 30
SRCS = [1, 2, 3, 5, 8, 13]
STEPS = 4
PLANES = ("plain", "pred", "hubs", "armed", "rows", "tomb")
FLAGS = {"plain": {"tpu_delta_max_edges": 0},
         "pred": {"tpu_delta_max_edges": 0},
         "hubs": {"tpu_delta_max_edges": 0, "tpu_degree_split_threshold": 8}}


@pytest.fixture()
def flags():
    cfg = get_config()
    yield cfg
    with cfg.lock:
        for k in ("tpu_delta_max_edges", "tpu_degree_split_threshold"):
            cfg.dynamic_layer.pop(k, None)


REAL = {name: getattr(bfs_mod, name)
        for name in ("build_bfs_fn_local", "build_bfs_fn")}


def _trips(monkeypatch, chunk):
    """Every BFS program the runtime builds from here on takes trips of
    `chunk` slots."""
    for name, real in REAL.items():
        monkeypatch.setattr(
            bfs_mod, name,
            lambda *a, _real=real, **kw: _real(*a, chunk=chunk, **kw))


def host_levels(st, srcs, steps, keep):
    """{vid: level} by a level-synchronous walk over the store."""
    level = {v: 0 for v in srcs}
    frontier = sorted(level)
    for depth in range(1, steps + 1):
        reached = set()
        for _s, _et, _rank, dst, props, _sgn in st.get_neighbors(
                "g", frontier, ["knows"], "out"):
            if keep(props) and dst not in level:
                reached.add(dst)
        for v in reached:
            level[v] = depth
        frontier = sorted(reached)
    return level


def _case(plane, parts):
    """(store, the writes to make after the pin, edge filter, the host's
    keep rule)."""
    st = _hubby_store(n=150) if plane == "hubs" else \
        store_p(parts, seed=23, n=150, avg_deg=5)
    cond, keep = None, lambda props: True
    if plane == "pred":
        cond = parse("GO FROM 1 OVER knows WHERE knows.w > 20 "
                     "YIELD dst(edge)").where.filter
        keep = lambda props: props["w"] is not NULL and props["w"] > 20  # noqa: E731

    def write():
        if plane == "rows":
            for v in (1, 2, 3):
                st.insert_edge("g", v, "knows", 140 + v, 0,
                               {"w": 60, "f": 0.5, "tag": "ann"})
        if plane == "tomb":
            for v in (1, 2):
                src, _, rank, dst, _, _ = next(iter(
                    st.get_neighbors("g", [v], ["knows"], "out")))
                st.delete_edge("g", src, "knows", dst, rank)
    return st, write, cond, keep


def _run(monkeypatch, flags, plane, parts, chunk):
    _trips(monkeypatch, chunk)
    flags.set_dynamic_many(FLAGS.get(plane, {}))
    st, write, cond, keep = _case(plane, parts)
    # a mesh holds one part a device: the hub store's eight
    rt = TpuRuntime(make_mesh(8 if plane == "hubs" and parts > 1 else parts))
    rt.bfs(st, "g", SRCS, ["knows"], "out", STEPS, edge_filter=cond)   # the pin
    write()
    dist, stats = rt.bfs(st, "g", SRCS, ["knows"], "out", STEPS,
                         edge_filter=cond)
    dev = rt.snapshots["g"]
    if plane in ("armed", "rows", "tomb"):
        held = dev.delta.host.total_edges()
        assert (held > 0) == (plane == "rows")
        assert (dev.delta.host.total_tombs() > 0) == (plane == "tomb")
    else:
        assert getattr(dev, "delta", None) is None
    if plane == "hubs":
        assert len(dev.host.hub_dense) > 0
    sd = st.space("g")
    P = dev.num_parts
    want = host_levels(st, SRCS, STEPS, keep)
    got = np.asarray(dist)
    for v in range(150):
        d = sd.dense_id(v)
        assert got[d % P, d // P] == want.get(v, -1), (plane, v)
    return stats, P


@pytest.mark.parametrize("parts", [1, 2], ids=["one-chip", "mesh"])
@pytest.mark.parametrize("plane", PLANES)
def test_levels_over_every_plane_by_need_and_straight_line(
        monkeypatch, flags, plane, parts):
    looped, P = _run(monkeypatch, flags, plane, parts, TRIP)
    whole, _ = _run(monkeypatch, flags, plane, parts, WHOLE)
    if parts == 1:
        assert looped.hop_edges == whole.hop_edges
        assert looped.bottom_up == whole.bottom_up
        assert looped.e_cap == whole.e_cap and looped.retries == whole.retries
        if plane not in ("rows", "tomb"):
            assert any(looped.bottom_up)      # the bottom-up body ran too
    else:
        # a mesh chooses a level's direction where its budget loops (PR 45):
        # the straight-line program has no choice, and the levels both took
        # top-down expand the same slots
        assert not any(whole.bottom_up)
        assert [(a, b) for a, b, up in zip(looped.hop_edges, whole.hop_edges,
                                           looped.bottom_up) if not up and a != b] == []
        if plane in ("plain", "pred", "armed"):
            assert any(looped.bottom_up)      # a shard's bottom-up body ran
    if plane in ("rows", "tomb"):
        # a plane that holds anything keeps every level top-down
        assert not any(looped.bottom_up)
    # every level's budget is whole trips of 64, and no level fills it
    assert whole.chunks_run == whole.chunks_budget == 0
    assert looped.chunks_budget == P * sum(e // TRIP for e in looped.e_cap)
    assert 0 < looped.chunks_run < looped.chunks_budget
