"""A person no friendship reached is a vertex to the algo plane as to the
benchmark's plain reference (PR 47): `vmask` holds it by its tag row,
PageRank's `n` counts it (it is dangling), WCC makes it a component of
one, SSSP leaves it out of its rows; and a run from it reaches itself
alone.  On the device kernels and on the host oracles."""
from __future__ import annotations

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import loader  # noqa: E402
from benchmarks.lib.reply import Reply  # noqa: E402
from benchmarks.reference.graph import RefGraph  # noqa: E402

from nebula_tpu.algo.engine import _algo_graph, run_algorithm  # noqa: E402
from nebula_tpu.algo.graph import blocks_for  # noqa: E402

BUILDER = loader.module("builders", "prebuilt_algo")
TEMPLATES = {t["name"]: t for t in loader.data("traffic", "algo3-single")["templates"]}
SCHEMA = {"tags": {"Person": {}}, "edges": {"KNOWS": {"weight": "double"}}}
N, ISOLATED = 11, 7


def tables():
    """0 - 1 - 2 - 3 and 4 - 5 - 6 - 8 - 9 - 10 as both rows of a pair with
    one weight; 7 is in no pair."""
    pairs = [(0, 1), (1, 2), (2, 3), (4, 5), (5, 6), (6, 8), (8, 9), (9, 10)]
    a, b = np.array(pairs).T
    w = np.linspace(0.1, 0.9, len(pairs))
    weight = np.concatenate([w, w])
    return {"n": N, "vertex": {}, "strings": {}, "edges": {"KNOWS": {
        "src": np.concatenate([a, b]), "dst": np.concatenate([b, a]), "weight": weight,
        "f": weight, "w": np.zeros(weight.size, np.int64)}}}


@pytest.fixture(scope="module")
def pinned():
    from benchmarks.builders.prebuilt_mesh import snapshot_from_pairs
    from benchmarks.builders.prebuilt_snapshot import SPACE, SnapshotStore
    from nebula_tpu.tpu.runtime import TpuRuntime
    t = tables()
    snap = BUILDER.with_tag_rows(snapshot_from_pairs(t, SCHEMA["edges"], 4, SPACE), SCHEMA["tags"])
    rt = TpuRuntime(n_devices=1)
    rt.pin_prebuilt(snap)
    yield rt, SnapshotStore(snap), RefGraph(t)
    rt.unpin(SPACE)


def test_the_tag_row_makes_the_isolated_person_a_vertex(pinned):
    _rt, store, _ref = pinned
    snap = store.snap
    g = _algo_graph(snap, blocks_for(snap, ["KNOWS"], "out"), None)
    assert g.n_vertices == N and g.vmask[:N].all() and not g.vmask[N:].any()
    assert g.out_degree()[ISOLATED] == 0
    # without the tag rows the edges' ends alone are vertices
    bare = type(snap)(space=snap.space, epoch=0, num_parts=snap.num_parts, vmax=snap.vmax,
                      num_vertices=snap.num_vertices, blocks=snap.blocks, pool=snap.pool,
                      dense_to_vid=snap.dense_to_vid)
    assert not _algo_graph(bare, blocks_for(bare, ["KNOWS"], "out"), None).vmask[ISOLATED]


@pytest.mark.parametrize("mode", ["device", "host"])
@pytest.mark.parametrize("name", ["pagerank", "wcc", "sssp"])
def test_the_program_and_the_reference_agree_on_the_isolated_person(pinned, name, mode):
    rt, store, ref = pinned
    t = TEMPLATES[name]
    op = loader.module("reference/ops", name)
    for start in (0, ISOLATED):
        params = dict({k: start if v == "$v" else v for k, v in t["params"].items()}, mode=mode)
        rows, info = run_algorithm(name, params, store.snap, store.space("snb"), rt=rt)
        assert info["mode"] == mode and info["n_vertices"] == N
        want = op.answer(ref, t, start)
        got = Reply(n_rows=len(rows), data=BUILDER.Rows(rows, BUILDER.OPS[name]))
        bad, gap, detail = op.compare(got, want)
        assert bad == 0 and (gap or 0.0) <= 1e-9, detail
        assert len(rows) == op.count(ref, t, start)
        by_vid = dict(rows)
        if name == "pagerank":
            assert len(rows) == N and sum(by_vid.values()) == pytest.approx(1.0)
            assert by_vid[ISOLATED] == min(by_vid.values())    # only the spread dangling mass
        elif name == "wcc":
            assert by_vid[ISOLATED] == ISOLATED and list(by_vid.values()).count(ISOLATED) == 1
            assert sorted(set(by_vid.values())) == [0, 4, ISOLATED]
        elif start == ISOLATED:
            assert rows == [[ISOLATED, 0.0]]
        else:
            assert sorted(by_vid) == [0, 1, 2, 3] and ISOLATED not in by_vid
