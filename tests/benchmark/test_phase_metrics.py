"""The statement phase ledger's metrics (PR 24), through the harness: a
traced rehearsal of a served cell and of the proxy cell prints every new
per-layer metric of that cell, and in the served cell the phases close
against an outside clock around the same handler."""
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

MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
SERVED = ["snb-sf1.go-8s", "snb-sf1.path-1s"]
ALL = ["snb-sf1.go-8s", "snb-sf100-proxy.go3", "snb-sf1.path-1s"]
NEW = {
    "graphd.parse_plan_ms": SERVED, "graphd.exec_self_ms": SERVED,
    "graphd.rpc_wait_ms": SERVED, "graphd.rpcs_per_stmt": SERVED,
    "graphd.encode_ms": SERVED, "graphd.untraced_ms": SERVED,
    "graphd.plan_cache_hit_share": SERVED, "client.decode_ms": SERVED,
    "dispatch.queue_ms": ALL, "dispatch.put_ms": ALL, "dispatch.fetch_ms": ALL,
    "dispatch.mat_ms": ALL, "dispatch.retries_per_stmt": ALL,
    "dispatch.refetches_per_stmt": ALL, "host.cpu_cores_busy": ALL,
}
LAYER = {"graphd": "graphd", "client": "client and wire", "dispatch": "device dispatch",
         "host": "host process"}
HANDLER = "rpc_server_latency_us{op=graph.execute,role=graphd}.sum"


@pytest.fixture
def jax_config_restored():
    """run.py's enable_compile_cache() sets jax.config options for the
    whole process; put them back for the test files that follow."""
    import jax
    keys = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in before.items():
        jax.config.update(k, v)


def test_the_manifest_names_the_fifteen_new_metrics():
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    assert [m["name"] for m in MANIFEST["per_layer"]][-len(NEW):] == list(NEW)
    for name, cells in NEW.items():
        m = by_name[name]
        assert m["workloads"] == cells and m["source"] == "program_counter"
        assert m["layer"] == LAYER[name.split(".")[0]]
        assert m["unit"] == ("%" if name.endswith("_share") else
                             "ms" if name.endswith("_ms") else "count")
        assert m["better"] == ("higher" if name.endswith("_share") else "lower")
        assert os.path.isfile(loader.path_of("layers", name, ".py"))


@pytest.mark.parametrize("cell", ["snb-sf1.go-8s", "snb-sf100-proxy.go3"])
def test_a_traced_rehearsal_prints_every_new_metric(cell, capsys, jax_config_restored):
    from nebula_tpu.utils.stats import stats
    c0 = stats().snapshot()
    rc = bench_run.main(["--seconds", "1", "--rehearse", "--workload", cell,
                         "--seed", "24", "--trace", "1"])
    c1 = stats().snapshot()
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and line["rehearsal"]["checks_passed"] is True, out[-3000:]
    want = {name for name, cells in NEW.items() if cell in cells}
    assert want <= set(line["metrics"]), want - set(line["metrics"])
    assert not (set(NEW) - want) & set(line["metrics"])
    got = {k: line["metrics"][k]["value"] for k in want}
    assert all(isinstance(v, (int, float)) and v >= 0 for v in got.values()), got
    assert got["dispatch.fetch_ms"] > 0 and got["dispatch.put_ms"] > 0
    assert got["dispatch.mat_ms"] > 0 and 0 < got["host.cpu_cores_busy"] < 64
    assert got["dispatch.refetches_per_stmt"] <= 1

    def moved(key):
        return c1.get(key, 0) - c0.get(key, 0)
    if cell in SERVED:
        # every statement the run served is one root trace; its phases
        # close against the RPC layer's own clock around the same handler
        phases = sum(moved(k) for k in c1 if k.startswith("stmt_phase_us{"))
        handler = moved(HANDLER)
        assert handler > 0 and abs(phases - handler) <= 0.05 * handler, (phases, handler)
        assert got["graphd.rpcs_per_stmt"] >= 9        # 8 part_stats + update_session
        assert got["graphd.plan_cache_hit_share"] > 50
        assert got["graphd.rpc_wait_ms"] > 0 and got["graphd.exec_self_ms"] > 0
        assert got["graphd.untraced_ms"] < 0.1 * handler / 1e3 / moved("num_queries")
    else:
        # below the statement: no trace, no phases, the device series move
        assert not any(moved(k) for k in c1 if k.startswith("stmt_phase_"))
        side = line["metrics"]["dispatch.hostside_ms"]["value"]
        parts = got["dispatch.put_ms"] + got["dispatch.fetch_ms"] + got["dispatch.mat_ms"]
        # hostside_ms is a mean over the window's statements, the
        # series over every statement the window's run sent
        assert parts == pytest.approx(side, rel=0.25)


def test_a_program_without_the_counters_reports_nothing(monkeypatch):
    """What the parent commit gives: no phase ledger, no device series
    in the snapshot -> every reader that needs one returns None."""
    from types import SimpleNamespace

    from benchmarks.lib import phases
    monkeypatch.setattr(phases, "stats", lambda: SimpleNamespace(
        snapshot=lambda: {"num_queries": 5, "plan_cache_entries": 1}))
    for served in (True, False):
        ctx = {"served": served, "records": [object()] * 3, "elapsed_s": 1.0,
               "counter": lambda name: 5 if name == "num_queries" else 0}
        for name in NEW:
            assert loader.module("layers", name).read(ctx) is None, name
