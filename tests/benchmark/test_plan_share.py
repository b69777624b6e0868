"""`kernel.plan_share` (PR 29): the reader on a hand-built `ctx`, its
manifest entry, and a traced rehearsal whose bitmaps are wider than the
program's threshold (hop.py `PLAN_CHUNK`), so that the hops' plans are
laid out from the frontier's members and the metric has something to
read; the configurations' own rehearsal sizes compile the whole-bitmap
plan and leave it out (test_benchmark.py's rehearsal of each cell)."""
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
NAME = "kernel.plan_share"


def test_plan_share_is_run_over_budget_of_the_windows_run():
    mod = loader.module("layers", NAME)
    moved = {"tpu_hop_plan_run": 98_304, "tpu_hop_plan_budget": 3_000_000}
    assert mod.read({"counter": lambda name: moved.get(name, 0)}) == pytest.approx(3.2768)
    # narrow bitmaps (the whole-bitmap plan), or a program without the counter: the parent
    assert mod.read({"counter": lambda name: 0}) is None
    assert mod.NEEDS == ("tpu_hop_plan_budget",)


def test_the_manifest_entry():
    m = next(m for m in MANIFEST["per_layer"] if m["name"] == NAME)
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == (
        "%", "lower", "program_counter", "kernels", "stmts_per_s")
    assert {"snb-sf300-proxy.go3-4chip", "snb-sf100-proxy.go3"} <= set(m["workloads"])
    cells = {w["name"] for w in MANIFEST["workloads"]}
    assert set(m["workloads"]) <= cells
    # each listed cell reports the end-to-end metric it moves
    e2e = next(e for e in MANIFEST["end_to_end"] if e["name"] == m["moves"])
    assert set(m["workloads"]) <= set(e2e.get("workloads", cells))


def test_a_rehearsal_over_wide_bitmaps_prints_the_plan_share(capsys, jax_config_restored, monkeypatch):  # noqa: F811
    """At 140,000 persons over 8 parts a bitmap is 17,500 ids wide, over
    the threshold of 2^14: every hop's plan is laid out from its members."""
    from nebula_tpu.tpu import hop
    from nebula_tpu.utils.stats import stats
    data = loader.data

    def wider(kind, name):
        d = data(kind, name)
        if (kind, name) == ("configs", "snb-sf100-proxy"):
            d["rehearse"].update(persons=140_000, degree=3)
            assert d["rehearse"]["persons"] // d["rehearse"]["parts"] > hop.PLAN_CHUNK
        return d
    monkeypatch.setattr(loader, "data", wider)
    c0 = stats().snapshot()
    rc = bench_run.main(["--seconds", "1", "--rehearse", "--workload", "snb-sf100-proxy.go3",
                         "--seed", "29", "--trace", "1"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    c1 = stats().snapshot()
    assert rc == 0 and line["rehearsal"]["checks_passed"] is True
    assert line["checks"]["rows_mismatched"]["value"] == 0
    run, budget = (c1.get(k, 0) - c0.get(k, 0) for k in ("tpu_hop_plan_run", "tpu_hop_plan_budget"))
    assert 0 < run < budget
    share = line["metrics"][NAME]
    assert share["unit"] == "%" and 0 < share["value"] < 100
