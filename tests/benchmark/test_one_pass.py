"""`mat.one_pass_share` and `mat.pooled_rows_share` (PR 40): the readers
on a hand-built `ctx`, their manifest entries found by name, and traced
rehearsals that print them.  Row assembly (tpu/runtime.py
`_block_columns`) observes four series once a statement: the rows it
assembled (`tpu_mat_rows`) and those whose pieces went through the
worker pool (`tpu_mat_pooled_rows`: a block of `POOL_MIN_ROWS` kept rows
or more), the numeric property columns it decoded
(`tpu_mat_numeric_cols`) and those whose NULL answer the assembling pass
gave (`tpu_mat_one_pass_cols`: the join of a device-gathered column's
halves).  The proxy cells' `YIELD dst, w, f` gathers both properties on
the device: 100.  At a rehearsal's sizes no statement reaches the
threshold: 0 rows pooled, in the served cell at its own size too."""
from __future__ import annotations

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.lib import loader  # noqa: E402

from test_phase_metrics import jax_config_restored  # noqa: E402,F401

MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
PROXY = ["snb-sf100-proxy.go3", "snb-sf300-proxy.go3-4chip"]
# name -> (cells it lists at least, better, the series its reader divides by, the one above it)
NEW = {
    "mat.one_pass_share": (PROXY, "higher", "tpu_mat_numeric_cols", "tpu_mat_one_pass_cols"),
    "mat.pooled_rows_share": (PROXY + ["snb-sf1.go-8s"], "higher", "tpu_mat_rows",
                              "tpu_mat_pooled_rows"),
}


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_manifest_entry(name):
    cells, better, _, _ = NEW[name]
    m = next(m for m in MANIFEST["per_layer"] if m["name"] == name)     # wherever it stands
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == \
        ("%", better, "program_counter", "device dispatch", "stmt_p50_ms")
    all_cells = {w["name"] for w in MANIFEST["workloads"]}
    assert set(cells) <= set(m["workloads"]) <= all_cells
    # each listed cell reports the end-to-end metric it moves
    e2e = next(e for e in MANIFEST["end_to_end"] if e["name"] == m["moves"])
    assert set(m["workloads"]) <= set(e2e.get("workloads", all_cells))
    assert os.path.isfile(loader.path_of("layers", name, ".py"))


def _read(name, moved):
    return loader.module("layers", name).read({"counter": lambda key: moved.get(key, 0)})


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_share_of_sums_over_the_windows_run(name):
    _, _, whole, part = NEW[name]
    # 30 statements, each of two columns (or 2 M rows), all of them through the mechanism
    assert _read(name, {whole + ".sum": 60.0, whole + ".count": 30,
                        part + ".sum": 60.0, part + ".count": 30}) == 100.0
    # a window that mixes in statements the mechanism passes by
    assert _read(name, {whole + ".sum": 80.0, whole + ".count": 40,
                        part + ".sum": 60.0, part + ".count": 40}) == 75.0
    # none of it: a reading of 0, not nothing
    assert _read(name, {whole + ".sum": 80.0, whole + ".count": 40,
                        part + ".count": 40}) == 0.0
    # a program without the series (the parent), a window that assembled nothing
    assert _read(name, {}) is None
    assert _read(name, {whole + ".count": 12, part + ".count": 12}) is None
    assert loader.module("layers", name).NEEDS == (whole + ".sum",)


def _rehearse(cell, capsys, seed):
    from nebula_tpu.utils.stats import stats
    c0 = stats().snapshot()
    rc = bench_run.main(["--seconds", "1", "--rehearse", "--workload", cell,
                         "--seed", str(seed), "--trace", "1"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    c1 = stats().snapshot()
    assert rc == 0 and line["rehearsal"]["checks_passed"] is True
    assert line["checks"]["rows_mismatched"]["value"] == 0
    assert line["checks"]["float_rel_gap"]["value"] == 0
    moved = {k: v - c0.get(k, 0) for k, v in c1.items() if isinstance(v, (int, float))}
    return line, moved


def _phase_sum_is_the_materialise_spans(line, moved):
    """`mat_concat` + `mat_decode` + `materialise` (self times) are the
    `device:materialise` spans: the three metrics read, none is null,
    and they sum to `dispatch.mat_ms`."""
    got = {k: line["metrics"][k]["value"] for k in ("mat.concat_ms", "mat.decode_ms", "mat.rest_ms")}
    assert all(isinstance(v, float) and v > 0 for v in got.values()), got
    assert sum(got.values()) == pytest.approx(line["metrics"]["dispatch.mat_ms"]["value"], rel=0.15)
    phases = sum(moved["stmt_phase_us{phase=%s}" % p] for p in ("mat_concat", "mat_decode", "materialise"))
    assert phases == pytest.approx(moved["tpu_mat_s.sum"] * 1e6, rel=0.15)


@pytest.mark.parametrize("cell", PROXY)
def test_the_proxy_cells_rehearsals_print_them(cell, capsys, jax_config_restored):  # noqa: F811
    line, moved = _rehearse(cell, capsys, 2147483640)
    # one observation of each series a statement; both yielded properties answered by the join
    assert moved["tpu_mat_numeric_cols.count"] == moved["tpu_mat_s.count"] > 0
    assert moved["tpu_mat_one_pass_cols.sum"] == moved["tpu_mat_numeric_cols.sum"] \
        == 2 * moved["tpu_mat_numeric_cols.count"]
    assert line["metrics"]["mat.one_pass_share"] == {"value": 100.0, "unit": "%"}
    # a rehearsal's statements are far under the threshold
    assert moved["tpu_mat_rows.sum"] > 0 and moved["tpu_mat_pooled_rows.sum"] == 0
    assert line["metrics"]["mat.pooled_rows_share"] == {"value": 0.0, "unit": "%"}
    if cell == "snb-sf100-proxy.go3":
        _phase_sum_is_the_materialise_spans(line, moved)


def test_side_by_side_the_phases_still_sum(capsys, jax_config_restored, monkeypatch):  # noqa: F811
    """With every statement over the threshold (as the one-chip cell's
    are at its own size) a block's columns are assembled under ONE
    concat span, so the phases do not count the overlapping passes
    twice."""
    from nebula_tpu.tpu import runtime
    if runtime._assembly_pool() is None:
        pytest.skip("one core: no pool is made")
    monkeypatch.setattr(runtime, "POOL_MIN_ROWS", 1)
    line, moved = _rehearse("snb-sf100-proxy.go3", capsys, 40)
    assert moved["tpu_mat_pooled_rows.sum"] == moved["tpu_mat_rows.sum"] > 0
    assert line["metrics"]["mat.pooled_rows_share"] == {"value": 100.0, "unit": "%"}
    assert line["metrics"]["mat.one_pass_share"] == {"value": 100.0, "unit": "%"}
    # one concat span a statement's block, not one a column
    assert moved["stmt_phase_n{phase=mat_concat}"] == moved["tpu_mat_s.count"]
    _phase_sum_is_the_materialise_spans(line, moved)


def test_the_served_cell_pools_nothing(capsys, jax_config_restored):  # noqa: F811
    """`snb-sf1.go-8s`: columns of some thousand rows at most stay on
    the statement's own thread; the metric reads 0, not nothing."""
    line, moved = _rehearse("snb-sf1.go-8s", capsys, 2147483641)
    assert moved["tpu_mat_rows.sum"] > 0 and moved["tpu_mat_pooled_rows.sum"] == 0
    assert line["metrics"]["mat.pooled_rows_share"] == {"value": 0.0, "unit": "%"}
    assert "mat.one_pass_share" not in line["metrics"]      # not this cell's
