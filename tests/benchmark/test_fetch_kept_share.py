"""`dispatch.fetch_kept_share` (PR 31): the reader on a hand-built `ctx`,
its manifest entry, and a traced rehearsal of a proxy cell and of a served
one, whose lines carry it between 0 and 100 and agree with the program's
two counters."""
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
NAME = "dispatch.fetch_kept_share"


@pytest.mark.parametrize("moved,want", [
    ({"tpu_fetch_bytes": 268_435_456, "tpu_fetch_bytes_kept": 251_658_240}, 93.75),
    ({"tpu_fetch_bytes": 4_096}, 0.0),          # meta alone: a BFS, an overflowed rung
    ({}, None),                                 # a program without the counters: the parent
], ids=["rows", "meta-alone", "no-counter"])
def test_kept_share_is_kept_over_fetched_bytes_of_the_windows_run(moved, want):
    mod = loader.module("layers", NAME)
    assert mod.read({"counter": lambda name: moved.get(name, 0)}) == want
    assert mod.NEEDS == ("tpu_fetch_bytes",)


def test_the_manifest_entry():
    m = next(m for m in MANIFEST["per_layer"] if m["name"] == NAME)
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == (
        "%", "higher", "program_counter", "device dispatch", "stmt_p50_ms")
    # every cell fetches from the device, and every cell reports what it moves
    assert "workloads" not in m
    assert "workloads" not in next(e for e in MANIFEST["end_to_end"] if e["name"] == m["moves"])
    assert m["layer"] in {x["layer"] for x in MANIFEST["per_layer"] if x["name"] != NAME}


@pytest.mark.parametrize("cell", ["snb-sf100-proxy.go3", "snb-sf1.go-8s"])
def test_a_traced_rehearsal_prints_the_kept_share(cell, capsys, jax_config_restored):  # noqa: F811
    from nebula_tpu.utils.stats import stats
    c0 = stats().snapshot()
    rc = bench_run.main(["--seconds", "1", "--rehearse", "--workload", cell, "--seed", "31", "--trace", "1"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    c1 = stats().snapshot()
    assert rc == 0 and line["rehearsal"]["checks_passed"] is True
    fetched, kept = (c1.get(k, 0) - c0.get(k, 0) for k in ("tpu_fetch_bytes", "tpu_fetch_bytes_kept"))
    assert 0 < kept < fetched
    share = line["metrics"][NAME]
    assert share["unit"] == "%" and 0 < share["value"] < 100
