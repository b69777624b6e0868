"""The five per-layer metrics PR 47 brings with the cell
`graphalytics-dg75-proxy.pr-wcc-sssp`: `algo.iter_ms`,
`algo.iters_per_stmt`, `algo.prepare_ms`, `algo.assemble_ms` and
`kernel.algo_roofline`.  Each reader on a hand-built `ctx` (and on a
parent's, which keeps no such counter: nothing, never an error), its
manifest entry found by its NAME wherever it stands, the byte model on
hand-counted shapes, and a traced rehearsal that prints the four that are
not read off the device trace against the program's own counters."""
from __future__ import annotations

import json
import os
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.lib import arith, loader  # noqa: E402
from benchmarks.lib.algo_bytes import ALGOS, VALUE_BYTES, algo_bytes  # noqa: E402
from benchmarks.reference.graph import RefGraph  # noqa: E402

from test_phase_metrics import jax_config_restored  # noqa: E402,F401

CELL, MIX = "graphalytics-dg75-proxy.pr-wcc-sssp", "algo3-single"
MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
TEMPLATES = {t["name"]: t for t in loader.data("traffic", MIX)["templates"]}
NEW = {"algo.iter_ms": ("ms", "lower", "program_counter", "analytics engine", "stmt_p50_ms"),
       "algo.iters_per_stmt": ("count", "lower", "program_counter", "analytics engine",
                               "stmt_p50_ms"),
       "algo.prepare_ms": ("ms", "lower", "program_counter", "analytics engine", "stmt_p50_ms"),
       "algo.assemble_ms": ("ms", "lower", "program_counter", "analytics engine", "stmt_p50_ms"),
       "kernel.algo_roofline": ("%", "higher", "device_trace", "kernels", "stmts_per_s")}


def ctx_of(moved, statements=6):
    return {"counter": lambda name: moved.get(name, 0), "served": False,
            "records": [object()] * statements}


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_manifest_entry(name):
    m = next(m for m in MANIFEST["per_layer"] if m["name"] == name)     # wherever it stands
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == NEW[name]
    assert CELL in m["workloads"]
    cells = {w["name"] for w in MANIFEST["workloads"]}
    e2e = next(e for e in MANIFEST["end_to_end"] if e["name"] == m["moves"])
    assert set(m["workloads"]) <= set(e2e.get("workloads", cells))
    assert os.path.isfile(loader.path_of("layers", name, ".py"))
    assert loader.module("layers", name).__doc__.startswith(f"`{name}`")


def test_iter_ms_is_the_mean_of_an_iteration_over_the_three_algorithms():
    read = loader.module("layers", "algo.iter_ms").read
    moved = {"algo_iter_us{algo=pagerank}.sum": 20e6, "algo_iter_us{algo=pagerank}.count": 20,
             "algo_iter_us{algo=wcc}.sum": 6e6, "algo_iter_us{algo=wcc}.count": 12,
             "algo_iter_us{algo=sssp}.sum": 14e6, "algo_iter_us{algo=sssp}.count": 48}
    assert read(ctx_of(moved)) == pytest.approx(40e6 / 80 / 1e3) == 500.0
    assert read(ctx_of({})) is None                       # no iteration ran
    assert ALGOS == ("pagerank", "wcc", "sssp")


def test_iters_per_stmt_divides_by_the_statements_sent():
    read = loader.module("layers", "algo.iters_per_stmt").read
    moved = {"algo_iterations{algo=pagerank}": 20, "algo_iterations{algo=wcc}": 12,
             "algo_iterations{algo=sssp}": 46}
    assert read(ctx_of(moved)) == 13.0
    assert read(ctx_of({})) is None and read(ctx_of(moved, statements=0)) is None


@pytest.mark.parametrize("name,series", [("algo.prepare_ms", "algo_prepare_s"),
                                         ("algo.assemble_ms", "algo_assemble_s")])
def test_the_series_readers_give_ms_a_statement_and_nothing_on_a_parent(name, series, monkeypatch):
    from benchmarks.lib import phases
    read = loader.module("layers", name).read
    # the program keeps the series (it has been observed in this process, or not at all)
    monkeypatch.setattr(phases, "stats", lambda: types.SimpleNamespace(
        snapshot=lambda: {f"{series}.sum": 9.0, f"{series}.count": 9}))
    assert read(ctx_of({f"{series}.sum": 1.5})) == pytest.approx(250.0)
    assert read(ctx_of({})) == 0.0                        # kept, and nothing of it in the window
    # a parent: no such key in its snapshot
    monkeypatch.setattr(phases, "stats", lambda: types.SimpleNamespace(snapshot=lambda: {}))
    assert read(ctx_of({f"{series}.sum": 1.5})) is None


def test_bytes_on_hand_counted_shapes():
    assert (arith.NBR_BYTES, VALUE_BYTES) == (4, 8)
    # ten iterations over 10 rows and 7 vertices: a row is two ids and a gathered double
    assert algo_bytes({"algo": "pagerank", "iterations": 10, "rows": 10, "vertices": 7}) == \
        10 * (10 * 16 + 7 * 16) == 2720
    # one pass: a row's two ids, a label read and written a vertex
    assert algo_bytes({"algo": "wcc", "rows": 10, "vertices": 7}) == 10 * 8 + 7 * 16 == 192
    # the rows out of the reached: two ids, the weight, the gathered distance
    assert algo_bytes({"algo": "sssp", "rows": 8, "vertices": 7}) == 8 * 24 + 7 * 16 == 304
    with pytest.raises(ValueError):
        algo_bytes({"algo": "cdlp", "rows": 1, "vertices": 1})
    # the configuration's own size, by shapes: what the issue reckoned (about 0.5 GB an iteration)
    full = algo_bytes({"algo": "pagerank", "iterations": 1, "rows": 68_400_000, "vertices": 633_432})
    assert 1.09e9 < full < 1.11e9


def test_roofline_reader_reckons_from_the_references_profiles():
    read = loader.module("layers", "kernel.algo_roofline").read
    z = np.zeros(2)
    g = RefGraph({"n": 3, "edges": {"KNOWS": {"src": np.array([0, 1]), "dst": np.array([1, 0]),
                                              "w": z, "f": np.ones(2)}}})
    ops = {n: loader.module("reference/ops", n) for n in ALGOS}
    for n in ALGOS:
        ops[n].answer(g, TEMPLATES[n], 0)
    rec = [types.SimpleNamespace(idx=i) for i in range(3)]
    reqs = [{"template": TEMPLATES[n], "start": 0} for n in ALGOS]
    ctx = {"trace": {"busy_s": 2.0}, "traced": rec, "peaks": arith.peaks_for("TPU v5 lite"),
           "requests": reqs}
    need = 10 * (2 * 16 + 3 * 16) + (2 * 8 + 3 * 16) + (2 * 24 + 3 * 16)
    assert read(ctx) == pytest.approx(100.0 * need / (2.0 * 819e9))
    # the statements that were traced, not the list: two of the three
    assert read(dict(ctx, traced=rec[1:])) == pytest.approx(
        100.0 * (need - 800) / (2.0 * 819e9))
    assert read(dict(ctx, trace=None)) is None and read(dict(ctx, peaks=None)) is None
    assert read(dict(ctx, traced=[])) is None and read(dict(ctx, trace={"busy_s": 0.0})) is None
    # a source the reference never ran from, or an operation with another profile or none
    assert read(dict(ctx, requests=reqs[:2] + [{"template": TEMPLATES["sssp"], "start": 2}])) is None
    bfs = loader.data("traffic", "bfs5-single")["templates"][0]
    go = loader.data("traffic", "go3-single")["templates"][0]
    assert read(dict(ctx, requests=[{"template": bfs, "start": 0}] * 3)) is None
    assert read(dict(ctx, requests=[{"template": go, "start": 0}] * 3)) is None


def test_a_traced_rehearsal_prints_them_against_the_programs_counters(capsys, jax_config_restored):  # noqa: F811,E501
    from nebula_tpu.utils.stats import stats
    rc = bench_run.main(["--seconds", "1", "--rehearse", "--workload", CELL, "--seed",
                         str(2 ** 31 + 4711), "--trace", "1"])
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and line["rehearsal"]["checks_passed"] is True, out[-3000:]
    got = {k: v for k, v in line["metrics"].items() if k in NEW}
    assert set(got) == set(NEW) - {"kernel.algo_roofline"}
    assert {k: v["unit"] for k, v in got.items()} == {k: NEW[k][0] for k in got}
    # PageRank's ten and what WCC and SSSP took: more than ten a statement cannot be the mean
    # of one fixed at ten and two that converge in a few on a graph this small... but more than
    # one is sure, and the mean iteration is a positive time
    assert 1 < got["algo.iters_per_stmt"]["value"] and got["algo.iter_ms"]["value"] > 0
    assert got["algo.prepare_ms"]["value"] == 0           # the warm-up prepared all three graphs
    assert got["algo.assemble_ms"]["value"] > 0
    c = stats().snapshot()
    assert c["algo_prepare_s.count"] >= 6 and c["algo_assemble_s.count"] >= line["attempted"]
