"""Tier-1 tests of what `snb-sf100-paths-proxy.bfs5` brings to the
benchmark (PR 41): the `bfs_levels` reference operation and the byte
count built on its level profile on hand-made graphs, the two controls
refused by its comparison, the three `kernel.bfs_*` readers on hand-built
`ctx`s with their manifest entries found by name, the `prebuilt_paths`
builder on one device among tier-1's eight, and an untraced and a traced
rehearsal of the cell that take a level bottom-up.  The cell's plain
rehearsals, control look-ups and pieces test are test_benchmark.py's
parametrised cases."""
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
from benchmarks.lib.bfs_bytes import LEVEL_BYTES, bfs_bytes  # noqa: E402
from benchmarks.lib.reply import Columns, Reply  # noqa: E402
from benchmarks.reference.graph import RefGraph  # noqa: E402

from test_phase_metrics import jax_config_restored  # noqa: E402,F401

CELL, CONFIG, MIX = "snb-sf100-paths-proxy.bfs5", "snb-sf100-paths-proxy", "bfs5-single"
MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CFG = loader.data("configs", CONFIG)
TEMPLATE = loader.data("traffic", MIX)["templates"][0]
OP = loader.module("reference/ops", "bfs_levels")
NEW = {"kernel.bfs_roofline": ("%", "device_trace"),
       "kernel.bfs_budget_fill": ("%", "program_counter"),
       "kernel.bfs_bottom_up_levels": ("count", "program_counter")}
COUNTERS = ("tpu_bfs_runs", "tpu_bfs_levels", "tpu_bfs_levels_bottom_up", "tpu_bfs_edges",
            "tpu_bfs_budget_slots")


def hand_graph():
    """0 - 1, 0 - 2, 1 - 3, 2 - 3, 3 - 4, 4 - 5, 5 - 6 in both directions,
    and 7 - 8 apart: from 0 the levels are 0 1 1 2 3 4 5, and 7, 8 out of
    reach."""
    pairs = [(0, 1), (0, 2), (1, 3), (2, 3), (3, 4), (4, 5), (5, 6), (7, 8)]
    src = np.array([a for a, b in pairs] + [b for a, b in pairs])
    dst = np.array([b for a, b in pairs] + [a for a, b in pairs])
    z = np.zeros(src.size)
    return RefGraph({"n": 9, "edges": {"KNOWS": {"src": src, "dst": dst, "w": z, "f": z}}})


# ---------------------------------------------------------------------------
# the reference operation and the bytes reckoned from it
# ---------------------------------------------------------------------------


def test_levels_of_a_hand_made_graph():
    g = hand_graph()
    want = OP.answer(g, TEMPLATE, 0)
    assert want["level"].tolist() == [0, 1, 1, 2, 3, 4, 5, -1, -1]
    assert OP.count(g, TEMPLATE, 0) == 7
    # the frontier entering each of the five levels and its out-edges
    assert OP.profile(TEMPLATE, 0) == ([[1, 2], [2, 4], [1, 3], [1, 2], [1, 2]], 9)
    assert OP.params(g, TEMPLATE, 0) == {"$t": "6"}       # the smallest vid on the deepest level
    three = dict(TEMPLATE, max_steps=3)
    assert OP.answer(g, three, 0)["level"].tolist() == [0, 1, 1, 2, 3, -1, -1, -1, -1]
    assert OP.answer(g, TEMPLATE, 7)["level"].tolist() == [-1] * 7 + [0, 1]
    # a component that runs dry: the levels left expand nothing
    assert OP.profile(TEMPLATE, 7) == ([[1, 1], [1, 1], [0, 0], [0, 0], [0, 0]], 9)
    assert OP.profile(TEMPLATE, 5) is None                # never asked for
    other = hand_graph()                                  # a second graph empties the memo
    assert OP.count(other, TEMPLATE, 3) == 7 and OP.profile(TEMPLATE, 0) is None


def test_the_comparison_is_by_position():
    g = hand_graph()
    want = OP.answer(g, TEMPLATE, 0)

    def reply(levels):
        return Reply(n_rows=7, data=Columns({"level": np.asarray(levels, np.int32)}))
    assert OP.compare(reply(want["level"]), want)[:2] == (0, None)
    swapped = want["level"].copy()
    swapped[[3, 4]] = swapped[[4, 3]]                     # the same multiset of levels
    assert OP.compare(reply(swapped), want)[0] == 2
    assert OP.compare(reply(want["level"][:-1]), want)[0] == 1


def test_bytes_a_top_down_bfs_has_to_move_on_the_hand_made_graph():
    g = hand_graph()
    OP.answer(g, TEMPLATE, 0)
    expanded, n = OP.profile(TEMPLATE, 0)
    # 6 frontier vertices x two row offsets, 13 neighbour ids, 9 levels written
    assert bfs_bytes(expanded, n) == 6 * 2 * 4 + 13 * 4 + 9 * 4 == 136
    assert (arith.INDPTR_BYTES, arith.NBR_BYTES, LEVEL_BYTES) == (4, 4, 4)
    assert bfs_bytes([], 0) == 0


@pytest.mark.parametrize("control", ["level_off_by_one", "level_unreached"])
def test_both_controls_are_refused(control):
    g = hand_graph()
    want = OP.answer(g, TEMPLATE, 0)
    broken = loader.module("controls", control).broken(want)
    assert broken is not None
    bad, gap, _ = OP.compare(broken, want)
    assert bad == 1 and gap is None
    got = broken.column("level")
    assert (got >= -1).all() and (want["level"] == [0, 1, 1, 2, 3, 4, 5, -1, -1]).all()
    if control == "level_unreached":
        assert (got < 0).sum() == 3
    # nobody reached but... nothing: a table with no level to break, and an answer with no table
    assert loader.module("controls", control).broken({"level": np.full(4, -1)}) is None
    assert loader.module("controls", control).broken([(0, 1, 3)]) is None


# ---------------------------------------------------------------------------
# the manifest and the readers
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_manifest_entry(name):
    m = next(m for m in MANIFEST["per_layer"] if m["name"] == name)     # wherever it stands
    assert (m["unit"], m["source"]) == NEW[name]
    assert (m["better"], m["layer"], m["moves"]) == ("higher", "kernels", "stmts_per_s")
    assert CELL in m["workloads"]
    cells = {w["name"] for w in MANIFEST["workloads"]}
    e2e = next(e for e in MANIFEST["end_to_end"] if e["name"] == m["moves"])
    assert set(m["workloads"]) <= set(e2e.get("workloads", cells))
    assert os.path.isfile(loader.path_of("layers", name, ".py"))


def test_the_cell_and_its_configuration():
    w = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, MIX, 1)
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    assert "path_reconstruction" in entry["reduced"] and len(entry["source"]) <= 200
    assert "FindShortestPath" in entry["source"] and "IC13" in entry["source"]
    assert CFG["builder"] == "prebuilt_paths" and CFG["reference"]["generator"] == "knows_symmetric"
    assert CFG["sizes"] == {"persons": 1_000_000, "degree": 30, "max_degree": 1000, "parts": 8}
    assert CFG["limits"]["rows_mismatched"] == 0 and "TpuRuntime.bfs" in CFG["fixes"]["entry"]
    # what it pins is what the GO proxies pin
    assert CFG["fixes"]["schema"] == loader.data("configs", "snb-sf300-proxy")["fixes"]["schema"]
    reported = {m["name"] for m in bench_run.metrics_for(MANIFEST, "end_to_end", CELL)}
    assert reported == {"stmt_p50_ms", "stmts_per_s", "setup_s"}
    mix = loader.data("traffic", MIX)
    assert (mix["sessions"], mix["requests"], mix["whole_rounds"], mix["trace_statements"]) == \
        (1, 6, True, 2)
    assert TEMPLATE["max_steps"] == 5 and "UPTO 5 STEPS" in TEMPLATE["text"]


def test_the_full_size_pins_what_the_configuration_says_by_shapes_alone():
    mesh = loader.module("builders", "prebuilt_mesh")
    sizes = CFG["sizes"]
    rows = sizes["persons"] * sizes["degree"]
    width = mesh.padded_width(rows // sizes["parts"])
    assert width == 4_194_304
    # the rows are within a fifth of a per cent of persons x degree from seed to seed and a
    # part is every eighth vid, so every seed's fullest part rounds up to this width
    assert {mesh.padded_width(int(f * rows / sizes["parts"])) for f in (0.99, 1.0, 1.05)} == {width}
    need = mesh.snapshot_bytes(sizes["persons"], sizes["parts"], width,
                               CFG["fixes"]["schema"]["edges"]["KNOWS"])
    assert need == 2_155_483_744 and f"{need:,}" in CFG["fixes"]["pinned_bytes"]
    # a part's fullest level stays under the ladder's cap
    from nebula_tpu.tpu.runtime import TpuRuntime
    assert width <= TpuRuntime(n_devices=1).max_cap


def _counter(moved):
    return lambda name: moved.get(name, 0)


def test_budget_fill_reader():
    mod = loader.module("layers", "kernel.bfs_budget_fill")
    assert mod.read({"counter": _counter(
        {"tpu_bfs_edges": 3_000, "tpu_bfs_budget_slots": 8 * 2_048 * 5})}) == \
        pytest.approx(100.0 * 3_000 / 81_920)
    assert mod.read({"counter": _counter({})}) is None    # the parent: no such counter
    assert mod.NEEDS == ("tpu_bfs_budget_slots",)


def test_bottom_up_levels_reader():
    mod = loader.module("layers", "kernel.bfs_bottom_up_levels")
    assert mod.read({"counter": _counter(
        {"tpu_bfs_runs": 12, "tpu_bfs_levels": 60, "tpu_bfs_levels_bottom_up": 18})}) == 1.5
    assert mod.read({"counter": _counter({"tpu_bfs_runs": 4})}) == 0.0     # never fired: a finding
    assert mod.read({"counter": _counter({})}) is None
    assert mod.NEEDS == ("tpu_bfs_runs",)


def test_roofline_reader_reckons_from_the_references_profile():
    read = loader.module("layers", "kernel.bfs_roofline").read
    g = hand_graph()
    OP.answer(g, TEMPLATE, 0)
    OP.answer(g, TEMPLATE, 7)
    rec = [types.SimpleNamespace(idx=0, stats=None), types.SimpleNamespace(idx=1, stats=None)]
    ctx = {"trace": {"busy_s": 2.0}, "traced": rec, "peaks": arith.peaks_for("TPU v5 lite"),
           "requests": [{"template": TEMPLATE, "start": 0}, {"template": TEMPLATE, "start": 7}]}
    # from 7: two frontier vertices, two ids, nine levels
    assert read(ctx) == pytest.approx(100.0 * (136 + 2 * 8 + 2 * 4 + 9 * 4) / (2.0 * 819e9))
    assert read(dict(ctx, trace=None)) is None and read(dict(ctx, peaks=None)) is None
    assert read(dict(ctx, traced=[])) is None
    # a request the reference never ran from, or an operation with no profile: nothing to read
    assert read(dict(ctx, requests=[{"template": TEMPLATE, "start": 4}] * 2)) is None
    go = loader.data("traffic", "go3-single")["templates"][0]
    assert read(dict(ctx, requests=[{"template": go, "start": 0}] * 2)) is None


# ---------------------------------------------------------------------------
# the builder and the rehearsals
# ---------------------------------------------------------------------------


def test_the_builder_stands_one_chip_up_among_eight_devices_and_answers_in_vid_order():
    gen = loader.module("reference/generators", CFG["reference"]["generator"])
    tables = gen.generate(CFG["rehearse"], 2 ** 31 + 4141)
    ref = RefGraph(tables, CFG["reference"]["dedupe_last"])
    said = []
    dep = loader.module("builders", CFG["builder"]).build(CFG, CFG["rehearse"], tables, said.append)
    try:
        assert dep.rt.local_mode and not dep.served
        assert set(dep.stages) == {"snapshot_s", "pin_s"}
        assert "both directions of KNOWS" in said[0] and len(said) == 1
        s = dep.open_session()
        start = int(np.argmax(ref.out_degree("KNOWS")))
        reply = s.execute({"template": TEMPLATE, "start": start})
        want = OP.answer(ref, TEMPLATE, start)
        assert reply.error is None and reply.n_rows == OP.count(ref, TEMPLATE, start)
        assert OP.compare(reply, want)[0] == 0
        assert reply.stats.bottom_up and any(reply.stats.bottom_up)
        assert "no operation 'go'" in s.execute({"template": {"op": "go"}, "start": 0}).error
    finally:
        dep.close()


@pytest.mark.parametrize("trace,control", [(0, "level_off_by_one"), (1, "level_unreached")])
def test_a_rehearsal_takes_a_level_bottom_up_and_prints_the_metrics(
        trace, control, capsys, jax_config_restored):  # noqa: F811
    from nebula_tpu.utils.stats import stats
    c0 = stats().snapshot()
    rc = bench_run.main(["--seconds", "1", "--rehearse", "--workload", CELL, "--seed",
                         str(2 ** 31 + 41 + trace), "--trace", str(trace), "--control", control])
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    c1 = stats().snapshot()
    moved = {k: c1.get(k, 0) - c0.get(k, 0) for k in COUNTERS + ("tpu_kernel_runs",)}
    assert rc == 0 and line["rehearsal"]["checks_passed"] is True, out[-3000:]
    assert line["failed"] == 0 and line["checks"]["rows_mismatched"]["value"] == 0
    assert line["control"]["correct"] is False and line["control"]["mismatched"] >= 1
    # every statement a BFS, one run of five levels each, at least one of them bottom-up
    assert moved["tpu_bfs_runs"] == moved["tpu_kernel_runs"] > 0
    assert moved["tpu_bfs_levels"] == 5 * moved["tpu_bfs_runs"]
    assert moved["tpu_bfs_levels_bottom_up"] >= moved["tpu_bfs_runs"]
    assert 0 < moved["tpu_bfs_edges"] <= moved["tpu_bfs_budget_slots"]
    if not trace:
        assert set(line["metrics"]) == {"stmt_p50_ms", "stmts_per_s", "setup_s"}
        return
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert got["kernel.bfs_bottom_up_levels"] == pytest.approx(
        moved["tpu_bfs_levels_bottom_up"] / moved["tpu_bfs_runs"], rel=0.05) and \
        1 <= got["kernel.bfs_bottom_up_levels"] <= 5
    assert 0 < got["kernel.bfs_budget_fill"] <= 100
    assert got["xla.compiles_in_window"] == 0
    # a share of the chip's peak: nothing to read where there is no chip (no peaks)
    assert "kernel.bfs_roofline" not in got
    assert "kernel.bfs_roofline" not in line["rehearsal"]["cpu_backend_readings"]
    # the traced slice closed on a statement boundary (`trace_statements`), with device work in it
    assert 0 < line["device"]["busy_s"] < line["device"]["window_s"]
    # what the other cells' metrics without a list find under `query:tpu.bfs`
    assert {"dispatch.device_ms", "dispatch.queue_ms", "dispatch.put_ms", "dispatch.fetch_ms",
            "dispatch.retries_per_stmt", "dispatch.refetches_per_stmt", "host.cpu_cores_busy",
            "dispatch.fetch_kept_share"} <= set(got)
    assert "dispatch.mat_ms" not in got                   # a BFS assembles no rows: not this cell's
