"""`kernel.expand_gathers` (PR 38): the reader on a hand-built `ctx`, its
manifest entry found by name, and traced rehearsals of the two proxy
cells that print it.  The series is `tpu_hop_slot_gathers`
(tpu/runtime.py `_escalate_locked`): the per-slot gathers of a traverse
program's last expansion stage, observed once a converged launch.  The
proxy cells' statements (`YIELD dst, w, f`, no filter, an unarmed
snapshot) read no rank, so their programs gather `nbr` and the
row-offset table alone: 2 at the cells' own sizes, where a bitmap is
wider than hop.py `PLAN_CHUNK`; at a configuration's rehearsal sizes the
whole-bitmap plan is compiled and its compact-row table is a third."""
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

NAME = "kernel.expand_gathers"
SERIES = "tpu_hop_slot_gathers"
MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def test_the_manifest_entry():
    m = next(m for m in MANIFEST["per_layer"] if m["name"] == NAME)     # wherever it stands
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == \
        ("count", "lower", "program_counter", "kernels", "stmts_per_s")
    assert {"snb-sf100-proxy.go3", "snb-sf300-proxy.go3-4chip"} <= set(m["workloads"])
    cells = {w["name"] for w in MANIFEST["workloads"]}
    assert set(m["workloads"]) <= cells
    # each listed cell reports the end-to-end metric it moves
    e2e = next(e for e in MANIFEST["end_to_end"] if e["name"] == m["moves"])
    assert set(m["workloads"]) <= set(e2e.get("workloads", cells))
    assert os.path.isfile(loader.path_of("layers", NAME, ".py"))


def _read(moved):
    return loader.module("layers", NAME).read({"counter": lambda name: moved.get(name, 0)})


def test_gathers_a_program_run_over_the_windows_run():
    # 21 launches of the rank-free program
    assert _read({SERIES + ".sum": 42.0, SERIES + ".count": 21}) == 2.0
    # a window that mixes a program with rank (3 a slot) among them
    assert _read({SERIES + ".sum": 45.0, SERIES + ".count": 20}) == 2.25
    # a program without the series (the parent), or a window with no launch
    assert _read({}) is None
    assert loader.module("layers", NAME).NEEDS == (SERIES + ".count",)


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
    # one observation a converged launch
    assert moved[SERIES + ".count"] == moved["tpu_kernel_runs"] > 0
    return line, moved


@pytest.mark.parametrize("cell", ["snb-sf100-proxy.go3", "snb-sf300-proxy.go3-4chip"])
def test_the_proxy_cells_rehearsals_print_it(cell, capsys, jax_config_restored):  # noqa: F811
    """Narrow bitmaps: `nbr`, the row offsets, the compact-row table;
    no rank."""
    line, moved = _rehearse(cell, capsys, 2147483685)
    assert _read(moved) == 3.0
    assert line["metrics"][NAME] == {"value": 3.0, "unit": "count"}


def test_a_rehearsal_over_wide_bitmaps_reads_two(capsys, jax_config_restored, monkeypatch):  # noqa: F811
    """At 140,000 persons over 8 parts a bitmap is 17,500 ids wide, over
    the threshold of 2^14, as the cell's 125,000 are: the member plan
    has no compact-row table, and what is left is what the chip runs."""
    from nebula_tpu.tpu import hop
    data = loader.data

    def wider(kind, name):
        d = data(kind, name)
        if (kind, name) == ("configs", "snb-sf100-proxy"):
            d["rehearse"].update(persons=140_000, degree=3)
            assert d["rehearse"]["persons"] // d["rehearse"]["parts"] > hop.PLAN_CHUNK
        return d
    monkeypatch.setattr(loader, "data", wider)
    line, moved = _rehearse("snb-sf100-proxy.go3", capsys, 38)
    assert moved["tpu_hop_plan_budget"] > 0
    assert line["metrics"][NAME] == {"value": 2.0, "unit": "count"}
