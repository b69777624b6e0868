"""PR 39's nine per-layer metrics, through the harness: each entry found
by its name, a traced rehearsal of both proxy cells and of `snb-sf1.go-8s`
prints every one listed for it (the proxy cells' statements are rooted by
the runtime itself now, so their phase ledger moves), a program without
the labels reads nothing, and the two trace readers on hand-built
intervals."""
from __future__ import annotations

import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.lib import loader, spans as S, trace as T  # noqa: E402

MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in MANIFEST["workloads"]]
P = ["snb-sf100-proxy.go3", "snb-sf300-proxy.go3-4chip"]
S4 = ["snb-sf1.go-8s", "snb-sf1.path-1s", "snb-sf1.write-read", "snb-sf1.write-read-8s-compact"]
DD = "device dispatch"
# name -> (cells, unit, better, source, layer, moves, the phase label its reader needs)
NEW = {
    "traverse.untraced_ms": (P, "ms", "lower", "program_counter", DD, "stmt_p50_ms", "other"),
    "traverse.host_self_ms": (P, "ms", "lower", "program_counter", DD, "stmt_p50_ms", "exec"),
    "dispatch.release_ms": (P + ["snb-sf1.go-8s", "snb-sf1.write-read-8s-compact"], "ms",
                            "lower", "program_counter", DD, "stmt_p50_ms", "release"),
    "mat.concat_ms": (["snb-sf100-proxy.go3", "snb-sf1.go-8s"], "ms", "lower",
                      "program_counter", DD, "stmt_p50_ms", "mat_concat"),
    "mat.decode_ms": (["snb-sf100-proxy.go3", "snb-sf1.go-8s"], "ms", "lower",
                      "program_counter", DD, "stmt_p50_ms", "mat_decode"),
    "mat.rest_ms": (["snb-sf100-proxy.go3", "snb-sf1.go-8s"], "ms", "lower",
                    "program_counter", DD, "stmt_p50_ms", "mat_concat"),
    "graphd.record_ms": (S4, "ms", "lower", "program_counter", "graphd", "stmt_p50_ms", "record"),
    "device.idle_attributed_share": (P + S4, "%", "higher", "device_trace", "device",
                                     "stmts_per_s", None),
    "dispatch.fetch_device_busy_share": (P + ["snb-sf1.go-8s"], "%", "lower", "device_trace", DD,
                                         "stmt_p50_ms", None),
}
# PR 24's metrics of the proxy cell, which `test_phase_metrics.py` held it to
# until its last assertion ended (conftest.py)
PR24_PROXY = ["dispatch.queue_ms", "dispatch.put_ms", "dispatch.fetch_ms", "dispatch.mat_ms",
              "dispatch.retries_per_stmt", "dispatch.refetches_per_stmt", "host.cpu_cores_busy"]


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


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_manifest_names_the_metric(name):
    cells, unit, better, source, layer, moves, label = NEW[name]
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    m = by_name[name]                                     # wherever it stands in the list
    assert set(cells) <= set(m["workloads"]) <= set(CELLS)
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == \
        (unit, better, source, layer, moves)
    mod = loader.module("layers", name)
    assert callable(mod.read)
    # a counter reader names the phase label it reads, so that a rehearsal in
    # which the label did not move may leave it out; a trace reader names none
    assert getattr(mod, "NEEDS", ()) == (() if label is None else
                                         (f"stmt_phase_n{{phase={label}}}",))


@pytest.mark.parametrize("cell", ["snb-sf100-proxy.go3", "snb-sf300-proxy.go3-4chip",
                                  "snb-sf1.go-8s"])
def test_a_traced_rehearsal_prints_every_new_metric_of_the_cell(cell, capsys,
                                                                  jax_config_restored):
    from nebula_tpu.utils.stats import stats
    c0 = stats().snapshot()
    rc = bench_run.main(["--seconds", "1", "--rehearse", "--workload", cell,
                         "--seed", "2147539024", "--trace", "1"])
    c1 = stats().snapshot()
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and line["rehearsal"]["checks_passed"] is True, out[-3000:]

    def moved(key):
        return c1.get(key, 0) - c0.get(key, 0)
    counted = {n for n, v in NEW.items() if cell in v[0] and v[3] == "program_counter"}
    traced = {n for n, v in NEW.items() if cell in v[0] and v[3] == "device_trace"}
    assert counted <= set(line["metrics"]), counted - set(line["metrics"])
    # off the chip a reading of the device's planes never stands among the metrics
    off_chip = line["rehearsal"]["cpu_backend_readings"]
    assert traced <= set(off_chip) and not traced & set(line["metrics"]), (traced, off_chip)
    assert not (set(NEW) - counted - traced) & (set(line["metrics"]) | set(off_chip))
    got = {k: line["metrics"][k]["value"] for k in counted}
    assert all(isinstance(v, (int, float)) and v >= 0 for v in got.values()), got
    assert 0 <= off_chip["device.idle_attributed_share"]["value"] <= 100
    assert 0 <= off_chip["dispatch.fetch_device_busy_share"]["value"] <= 100
    # ONE root a statement, whichever way it entered
    roots = moved("stmt_phase_n{phase=other}")
    if cell in P:
        # the runtime's own root: one a kernel run (no retry in a rehearsal)
        assert roots == moved("tpu_kernel_runs") > 0
        assert got["traverse.host_self_ms"] > 0 and got["dispatch.release_ms"] > 0
        # what it keeps to itself is a small part of a statement
        phases = sum(moved(k) for k in c1 if k.startswith("stmt_phase_us{"))
        assert got["traverse.untraced_ms"] * 1e3 * roots < 0.1 * phases
        # every idle gap inside a statement is named by the program's phases
        for label, _ in line["breakdown"]["idle_gaps"]:
            if "statement" in label and "between statements" not in label:
                assert re.match(r"(\d+x[a-z_]+ )*\d+x[a-z_]+; ", label), label
        # what `test_phase_metrics.py` held this cell to
        assert set(PR24_PROXY) <= set(line["metrics"])
        if cell == "snb-sf100-proxy.go3":
            parts = sum(line["metrics"][k]["value"]
                        for k in ("dispatch.put_ms", "dispatch.fetch_ms", "dispatch.mat_ms"))
            assert parts == pytest.approx(
                line["metrics"]["dispatch.hostside_ms"]["value"], rel=0.25)
    else:
        assert roots == moved("num_queries") > 0          # no second root under graphd
        assert got["graphd.record_ms"] > 0
        assert "traverse.untraced_ms" not in line["metrics"]
    if "mat.concat_ms" in counted:
        # the three parts of row assembly are its whole span
        mat = got["mat.concat_ms"] + got["mat.decode_ms"] + got["mat.rest_ms"]
        assert got["mat.concat_ms"] > 0 and got["mat.rest_ms"] > 0
        assert mat == pytest.approx(line["metrics"]["dispatch.mat_ms"]["value"], rel=0.15)


def test_a_program_without_the_labels_reports_nothing(monkeypatch):
    """What the parent commit gives: no root below graphd, so no phase
    ledger in a proxy cell, and none of the new labels in a served one ->
    every counter reader returns None.  A parent's `materialise` is the
    WHOLE span, which `mat.rest_ms` must not read as its rest."""
    from types import SimpleNamespace

    from benchmarks.lib import phases
    parent = {"num_queries": 5, "stmt_phase_us{phase=materialise}": 900,
              "stmt_phase_n{phase=materialise}": 5}
    monkeypatch.setattr(phases, "stats", lambda: SimpleNamespace(snapshot=lambda: parent))
    for served in (True, False):
        ctx = {"served": served, "records": [object()] * 3, "elapsed_s": 1.0,
               "counter": lambda name: parent.get(name, 0), "events": None, "trace": None}
        for name in NEW:
            assert loader.module("layers", name).read(ctx) is None, (name, served)
    # ... and with the labels, per statement: by graphd's count, or by what the driver sent
    change = dict(parent, **{"stmt_phase_us{phase=mat_concat}": 300,
                             "stmt_phase_us{phase=release}": 60})
    monkeypatch.setattr(phases, "stats", lambda: SimpleNamespace(snapshot=lambda: change))
    for served, n in ((True, 5), (False, 3)):
        ctx = {"served": served, "records": [object()] * 3,
               "counter": lambda name: change.get(name, 0)}
        assert loader.module("layers", "mat.rest_ms").read(ctx) == pytest.approx(0.9 / n)
        assert loader.module("layers", "dispatch.release_ms").read(ctx) == pytest.approx(0.06 / n)
        assert loader.module("layers", "mat.decode_ms").read(ctx) is None


def events(devices, spans, t0=0, t1=1000):
    return {"devices": devices, "spans": spans,
            "marks": {T.SLICE_BEGIN: [(t0, t0)], T.SLICE_END: [(t1, t1)], T.STMT: []}}


def test_idle_attributed_share_on_hand_built_intervals():
    read = loader.module("layers", "device.idle_attributed_share").read
    # two planes; some plane is busy in [100, 300) and [500, 600): holes
    # [0, 100), [300, 500), [600, 1000) = 700 ns
    dev = {"/device:TPU:0": [("a", 100, 250)], "/device:TPU:1": [("b", 200, 300), ("c", 500, 600)]}
    root = ("query:tpu.traverse", "t#1", 0, 1000)
    assert read({"events": events(dev, [])}) is None                  # no span: the parent in P
    assert read({"events": events(dev, [root])}) == 0.0               # a root alone names nothing
    # a span over half of the middle hole (and over busy time, which is not idle)
    half = ("device:fetch", "t#1", 250, 400)
    assert read({"events": events(dev, [root, half])}) == pytest.approx(100 * 100 / 700)
    # spans on two lines that overlap count once; one reaching past the slice is clipped
    more = [root, half, ("tpu:prep", "t#2", 350, 450), ("device:materialise", "t#2", 900, 1500)]
    assert read({"events": events(dev, more)}) == pytest.approx(100 * (150 + 100) / 700)
    # every hole under some span
    cover = [root, ("exec:TpuTraverse", "t#1", 0, 1000)]
    assert read({"events": events(dev, cover)}) == pytest.approx(100.0)
    assert read({"events": events({"/device:TPU:0": [("a", 0, 1000)]}, cover)}) is None  # no hole
    assert read({"events": None}) is None


def test_fetch_device_busy_share_on_hand_built_intervals():
    read = loader.module("layers", "dispatch.fetch_device_busy_share").read
    dev = {"/device:TPU:0": [("hop", 100, 300)], "/device:TPU:1": [("slice", 420, 430)]}
    assert read({"events": events(dev, [("device:put", "t#1", 0, 50)])}) is None
    # one session: the fetch meets only its own slice program, 10 of 100 ns
    own = [("device:fetch", "t#1", 400, 480), ("device:fetch.rows", "t#1", 480, 500)]
    assert read({"events": events(dev, own)}) == pytest.approx(10.0)
    # a second session's fetch open while the first one's hop program runs:
    # [250, 300) of its [250, 350) is behind that program
    both = own + [("device:fetch", "t#2", 250, 350)]
    assert read({"events": events(dev, both)}) == pytest.approx(100 * (10 + 50) / 200)
    # the nested transfer inside `device:fetch.rows` is the same ns, not more
    nested = own + [("device:fetch", "t#1", 485, 495)]
    assert read({"events": events(dev, nested)}) == pytest.approx(10.0)


def test_interval_helpers():
    assert S.clipped([(0, 10), (5, 20), (30, 40), (90, 200)], 8, 100) == [[8, 20], [30, 40], [90, 100]]
    assert S.overlap_ns([[0, 10], [20, 30]], [[5, 25]]) == 10
    assert S.overlap_ns([], [[0, 5]]) == 0 and S.length_ns([[0, 10], [20, 25]]) == 15
