"""The benchmark's own tests (tier-1 collects this file): the manifest
against the contract's character and cross-reference rules, the metric
arithmetic on known values, the trace reduction on a recorded trace, one
`--rehearse` run per cell in-process on the CPU backend, the negative
controls, and the proof that a later PR adds a metric or a mix as files."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.lib import arith, loader, trace as T  # noqa: E402
from benchmarks.lib.requests import make_requests  # noqa: E402
from benchmarks.reference.graph import RefGraph, same_rows  # noqa: E402

MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in MANIFEST["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BIG_SEED = 2 ** 31 + 12345
CONTROLS = sorted(f[:-3] for f in os.listdir(os.path.join(ROOT, "benchmarks", "controls"))
                  if f.endswith(".py"))


def generate(name, sizes, seed):
    return loader.module("reference/generators", name).generate(sizes, seed)


def broken(control, want):
    return loader.module("controls", control).broken(want)


# ---------------------------------------------------------------------------
# the manifest
# ---------------------------------------------------------------------------


def test_manifest_has_exactly_the_contract_keys():
    assert sorted(MANIFEST) == ["command", "configs", "end_to_end", "paths", "per_layer",
                                "run_seconds", "workloads"]
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert MANIFEST["command"][-1] == "benchmarks/run.py"
    assert len(json.dumps(MANIFEST)) < 64 * 1024
    for p in MANIFEST["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))


def test_names_units_and_lines_use_the_allowed_characters():
    metrics = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    for m in metrics:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for m in MANIFEST["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in MANIFEST["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k) for k in c["reduced"])
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"] and "\t" not in w["why"]
    assert "setup_s" in names


@pytest.mark.parametrize("cell", CELLS)
def test_every_piece_a_cell_names_is_a_file_that_exists(cell):
    w = next(x for x in MANIFEST["workloads"] if x["name"] == cell)
    cfg_entry = next(c for c in MANIFEST["configs"] if c["name"] == w["config"])
    assert os.path.isfile(os.path.join(ROOT, cfg_entry["file"]))
    cfg = loader.data("configs", w["config"])
    mix = loader.data("traffic", w["traffic"])
    assert cfg["name"] == w["config"] and cfg["chips"] == w["chips"]
    assert cfg["source"] == cfg_entry["source"] and cfg["reduced"] == cfg_entry["reduced"]
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])         # a reason for every departure
    assert hasattr(loader.module("reference/generators", cfg["reference"]["generator"]), "generate")
    assert set(cfg["fixes"]["schema"]) == {"tags", "edges"}
    for props in cfg["fixes"]["schema"]["edges"].values():
        assert set(props.values()) <= set(arith.TYPE_BYTES)
    assert set(mix["start_vertex"]) == {"etype"}
    assert hasattr(loader.module("builders", cfg["builder"]), "build")
    assert hasattr(loader.module("drivers", mix["driver"]), "run")
    for t in mix["templates"]:
        op = loader.module("reference/ops", t["op"])
        assert all(hasattr(op, f) for f in ("answer", "count", "compare"))
    for group, kind in (("per_layer", "layers"), ("end_to_end", "end_to_end")):
        for m in bench_run.metrics_for(MANIFEST, group, cell):
            assert hasattr(loader.module(kind, m["name"]), "read")
    reported = [m["name"] for m in bench_run.metrics_for(MANIFEST, "end_to_end", cell)]
    assert "setup_s" in reported and len(reported) >= 2
    assert bench_run.metrics_for(MANIFEST, "per_layer", cell)


def test_every_layer_metric_moves_a_metric_its_cells_report():
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    for m in MANIFEST["per_layer"]:
        assert m["moves"] in e2e, m
        for cell in m.get("workloads", CELLS):
            assert cell in e2e[m["moves"]].get("workloads", CELLS), (m["name"], cell)
    layers = {}
    for m in MANIFEST["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())      # one spelling per layer


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("samples,p,want", [
    ([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 50, 5), ([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 95, 10),
    (list(range(1, 201)), 95, 190), ([7.5], 95, 7.5), ([3, 1, 2], 50, 2)])
def test_percentile_is_nearest_rank(samples, p, want):
    assert arith.percentile(samples, p) == want


def test_spread_is_the_contracts():
    vals = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    import statistics
    q = statistics.quantiles(vals, n=4)
    assert arith.spread(vals) == pytest.approx((q[2] - q[0]) / statistics.median(vals))


def test_byte_model_on_a_hand_computed_three_hop_go():
    # frontiers of 1, 30 and 500 vertices expand 30, 600 and 9,000 edges;
    # YIELD dst, w, f reads 4 + 8 + 8 bytes per last-hop edge
    want = (1 * 8 + 30 * 4) + (30 * 8 + 600 * 4) + (500 * 8 + 9000 * 20)
    schema = {"w": "int", "f": "double", "city": "string"}
    assert arith.hop_bytes([30, 600, 9000], [1, 30, 500], ["w", "f"], schema) == want == 186768
    # a property that is yielded and filtered is read once; dst alone reads the id only
    assert arith.hop_bytes([10], [1], ["w", "w"], schema) == 8 + 10 * 12
    assert arith.hop_bytes([10], [1], [], schema) == 8 + 10 * 4
    with pytest.raises(KeyError):                          # a property the schema lacks
        arith.hop_bytes([10], [1], ["age"], schema)


def test_roofline_reader_takes_the_property_widths_from_the_configurations_schema():
    class Stats:
        hop_edges, frontier_sizes = [30, 600, 9000], [1, 30, 500]

    class Rec:
        idx, stats = 0, Stats()

    tpl = loader.data("traffic", "go3-single")["templates"][0]
    ctx = {"trace": {"busy_s": 2.0}, "traced": [Rec()], "peaks": arith.peaks_for("TPU v5 lite"),
           "requests": [{"template": tpl}],
           "schema": loader.data("configs", "snb-sf100-proxy")["fixes"]["schema"]}
    read = loader.module("layers", "kernel.hop_roofline").read
    assert read(ctx) == pytest.approx(100.0 * 186768 / (2.0 * 819e9))
    filtered = dict(tpl, cols=["d"], w_gt=50)              # a filter reads w though only dst is yielded
    assert read(dict(ctx, requests=[{"template": filtered}])) == pytest.approx(
        100.0 * ((1 * 8 + 30 * 4) + (30 * 8 + 600 * 4) + (500 * 8 + 9000 * 12)) / (2.0 * 819e9))
    assert read(dict(ctx, trace=None)) is None


def test_peaks_table_knows_the_v5e_and_refuses_others():
    assert arith.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        arith.peaks_for("cpu")


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------


def _ns(ms):
    return int(ms * 1e6)


def test_trace_reduction_on_hand_built_intervals():
    loaded = {"devices": {"/device:TPU:0": [("a", _ns(10), _ns(20)), ("b", _ns(15), _ns(30)),
                                            ("a", _ns(60), _ns(70)), ("c", _ns(95), _ns(120))]},
              "marks": {T.SLICE_BEGIN: [(0, 0)], T.SLICE_END: [(_ns(100), _ns(100))],
                        T.STMT: [(_ns(5), _ns(80))]}, "planes": ["/device:TPU:0"]}
    r = T.reduce(loaded, sessions=1)
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.035)            # [10,30) + [60,70) + [95,100)
    assert r["idle_share"] == pytest.approx(65.0)
    assert r["device_ops"] == [["a", pytest.approx(0.020)], ["b", pytest.approx(0.015)],
                               ["c", pytest.approx(0.005)]]
    assert r["idle_gaps"][0] == ["inside a statement, between device operations",
                                 pytest.approx(0.030)]
    labels = {g[0] for g in r["idle_gaps"]}
    assert "between statements" in labels                 # [80, 95)
    assert "inside a statement, before its first device operation" in labels   # [5, 10)
    two = T.reduce(dict(loaded, devices={"/device:TPU:0": loaded["devices"]["/device:TPU:0"],
                                         "/device:TPU:1": []}))
    assert two["busy_s"] == pytest.approx(0.0175)         # mean over the chips


def test_trace_reduction_on_the_recorded_trace():
    """benchmarks/testdata/cpu_slice.xplane.pb: recorded here on the CPU
    backend — a slice of three `bench:stmt` spans, each one jitted matmul
    and a 10 ms sleep."""
    r = T.reduce(T.load(os.path.join(ROOT, "benchmarks", "testdata", "cpu_slice.xplane.pb")))
    assert 0.03 < r["window_s"] < 2.0
    assert 0 < r["busy_s"] < r["window_s"]
    assert 0 < r["idle_share"] < 100
    assert r["device_ops"] and any("dot" in n for n, _ in r["device_ops"])
    assert all(a[1] >= b[1] for a, b in zip(r["device_ops"], r["device_ops"][1:]))
    assert len(r["idle_gaps"]) <= 5 and r["idle_gaps"][0][1] >= 0.005
    assert sum(d for _, d in r["device_ops"]) >= r["busy_s"] * 0.5


# ---------------------------------------------------------------------------
# the reference and the comparison
# ---------------------------------------------------------------------------


def _tiny():
    # 0->1, 0->2, 1->3, 2->3, 3->0 ; a second 0->1 row overwrites the first
    e = {"src": np.array([0, 0, 1, 2, 3, 0]), "dst": np.array([1, 2, 3, 3, 0, 1]),
         "w": np.array([10, 60, 70, 40, 90, 55]), "f": np.array([.1, .2, .3, .4, .5, .6])}
    return e


def test_reference_go_on_a_hand_graph():
    g = RefGraph({"n": 4, "edges": {"KNOWS": _tiny()}}, dedupe_last=True)
    assert g.n_edges("KNOWS") == 5
    cols, n, hops = g.go([0], 1, ["KNOWS"], cols=("d", "w", "f"))
    assert sorted(zip(cols["d"].tolist(), cols["w"].tolist(), cols["f"].tolist())) == \
        [(1, 55, .6), (2, 60, .2)]
    cols, n, hops = g.go([0], 3, ["KNOWS"], cols=("d",))
    assert (n, hops, cols["d"].tolist()) == (1, [2, 2, 1], [0])     # frontier {1,2} -> {3} -> 0
    assert g.go([0], 2, ["KNOWS"], w_gt=50, cols=("d", "w"))[1] == 1
    assert g.go([0], 2, ["KNOWS"], w_gt=50, count_only=True)[1] == 1
    assert g.go([0], 3, ["KNOWS"], count_only=True)[1] == 1
    keep = RefGraph({"n": 4, "edges": {"KNOWS": _tiny()}}, dedupe_last=False)
    assert keep.n_edges("KNOWS") == 6
    assert g.trail_count([0], "KNOWS", 4) == 2 + 2 + 2 + 2
    assert g.shortest_paths(0, 3, "KNOWS", 4) == [(0, 1, 3), (0, 2, 3)]
    assert g.subgraph(0, "KNOWS", 1) == [([0], [(0, 1), (0, 2)]), ([1, 2], [])]


def test_same_rows_is_a_multiset_comparison_with_a_float_gap():
    rng = np.random.default_rng(3)
    want = {"d": rng.integers(0, 50, 1000), "w": rng.integers(0, 100, 1000), "f": rng.random(1000)}
    perm = rng.permutation(1000)
    got = {k: v[perm] for k, v in want.items()}
    assert same_rows(got, want)[:2] == (0, 0.0)
    narrow = dict(got, d=got["d"].astype(np.int8))        # transport-narrowed ints are fine
    assert same_rows(narrow, want)[:2] == (0, 0.0)
    assert same_rows(dict(got, f=got["f"].astype(np.float32)), want)[0] > 0
    ints = {"d": want["d"], "w": want["w"]}
    assert same_rows({k: v[perm] for k, v in ints.items()}, ints)[:2] == (0, None)
    swapped = dict(got, w=got["w"][::-1].copy())          # right columns, wrong rows
    assert same_rows(swapped, want)[0] > 0


def test_same_rows_counts_a_double_that_is_not_finite_as_a_row_that_differs():
    rng = np.random.default_rng(4)
    want = {"d": rng.integers(0, 50, 1000), "w": rng.integers(0, 100, 1000), "f": rng.random(1000)}
    perm = rng.permutation(1000)
    got = {k: v[perm] for k, v in want.items()}
    for value in (np.nan, np.inf, -np.inf):
        one = dict(got, f=got["f"].copy())
        one["f"][500] = value
        bad, gap, _ = same_rows(one, want)
        assert bad >= 1 and gap == 0.0, (value, bad, gap)
    bad, gap, _ = same_rows(dict(got, f=np.full(1000, np.nan)), want)
    assert bad == 1000
    zero = dict(want, f=want["f"].copy())
    zero["f"][0] = 0.0                                    # a stored 0.0 against a value: no limit holds
    assert same_rows(dict(zero, f=want["f"]), zero)[1] == np.inf
    both = dict(want, f=want["f"].copy())
    both["f"][3] = np.nan                                 # the same bits on both sides are the same row
    assert same_rows({k: v.copy() for k, v in both.items()}, both)[:2] == (0, 0.0)


@pytest.mark.parametrize("control", CONTROLS)
def test_every_control_is_refused_by_the_comparison(control):
    rng = np.random.default_rng(5)
    want = {"d": rng.integers(0, 50, 400), "w": rng.integers(0, 100, 400), "f": rng.random(400)}
    bad, gap, _ = same_rows(broken(control, want).cols, want)
    assert bad >= 1 or gap > 1e-9, (control, bad, gap)    # far over limits.float_rel_gap
    if control == "f32":
        assert bad == 0 and 1e-9 < gap < 1e-6             # float32 rounding: the limit refuses it
    if control == "nan_f":
        assert bad == 400
    ints = {"d": want["d"], "w": want["w"]}
    b = broken(control, ints)
    assert (b is None) == (control in ("f32", "nan_f"))   # nothing to break without a double
    assert b is None or same_rows(b.cols, ints)[0] >= 1
    assert broken(control, [(0, 1, 3)]) is None           # paths have no column to break


def test_generators_repeat_per_seed_and_take_a_large_seed():
    a = generate("snb_tables", {"persons": 200, "degree": 5}, BIG_SEED)
    b = generate("snb_tables", {"persons": 200, "degree": 5}, BIG_SEED)
    c = generate("snb_tables", {"persons": 200, "degree": 5}, BIG_SEED + 1)
    assert np.array_equal(a["edges"]["KNOWS"]["f"], b["edges"]["KNOWS"]["f"])
    assert not np.array_equal(a["edges"]["KNOWS"]["src"][:50], c["edges"]["KNOWS"]["src"][:50])
    assert set(a["vertex"]) == {"age", "name"} and len(a["vertex"]["name"]) == a["n"] == 200
    s = generate("social_arrays", {"persons": 500, "degree": 6}, BIG_SEED)
    assert set(s["edges"]["KNOWS"]) >= {"src", "dst", "w", "f", "city"}
    assert s["edges"]["KNOWS"]["city"].max() < len(s["strings"]["city"])


@pytest.mark.parametrize("mix_name", sorted({w["traffic"] for w in MANIFEST["workloads"]}))
def test_request_list_is_fixed_by_the_seed_and_stratified(mix_name):
    mix = loader.data("traffic", mix_name)
    t = generate("snb_tables", {"persons": 300, "degree": 6}, BIG_SEED)
    ref = RefGraph(t, True)
    one, two = make_requests(mix, ref, BIG_SEED), make_requests(mix, ref, BIG_SEED)
    assert [(r["text"], r["rows"]) for r in one] == [(r["text"], r["rows"]) for r in two]
    assert len(one) == mix["requests"] and [r["idx"] for r in one] == list(range(len(one)))
    per = {}
    for r in one:
        per[r["template"]["name"]] = per.get(r["template"]["name"], 0) + 1
        assert "$" not in r["text"]
    assert len(set(per.values())) == 1                    # equal shares
    assert one[-1]["rows"] == max(r["rows"] for r in one)  # the cycle ends with its heaviest
    other = make_requests(mix, ref, BIG_SEED + 1)
    assert [r["text"] for r in other] != [r["text"] for r in one]


# ---------------------------------------------------------------------------
# rehearsals: the whole run, in-process, on the CPU backend
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def jax_config_restored():
    import jax
    keys = ("jax_compilation_cache_dir", "jax_persistent_cache_min_entry_size_bytes",
            "jax_persistent_cache_min_compile_time_secs")
    before = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in before.items():
        jax.config.update(k, v)


def _rehearse(capsys, *argv, wrap_session=None):
    rc = bench_run.main(["--seconds", "1", "--rehearse", *argv], wrap_session=wrap_session)
    out = capsys.readouterr().out
    return rc, json.loads(out.strip().splitlines()[-1]), out


# per cell, the control of its untraced and of its traced rehearsal
CONTROL = {"snb-sf1.go-8s": ("f32", "drop_row"), "snb-sf100-proxy.go3": ("nan_f", "f32"),
           "snb-sf1.path-1s": ("bad_row", "bad_row")}


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_each_cell(cell, trace, capsys, jax_config_restored):
    rc, line, out = _rehearse(capsys, "--workload", cell, "--seed", str(BIG_SEED + trace),
                              "--trace", str(trace), "--control", CONTROL[cell][trace])
    assert rc == 0
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is False                       # a rehearsal is never a chip result
    assert line["rehearsal"]["checks_passed"] is True, out[-3000:]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    assert line["control"]["correct"] is False            # the check refuses the control
    group = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in bench_run.metrics_for(MANIFEST, group, cell)
            if m["source"] != "device_trace"}
    assert want <= set(line["metrics"]), (want, line["metrics"])
    for m in line["metrics"].values():
        assert isinstance(m["value"], (int, float)) and m["unit"]
    if trace:
        assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > line["device"]["busy_s"]
        assert len(line["breakdown"]["device_ops"]) <= 10 and len(line["breakdown"]["idle_gaps"]) <= 10
        assert "device.idle_share" in line["rehearsal"]["cpu_backend_readings"]
        assert "device.idle_share" not in line["metrics"]  # no CPU number under a device metric
    else:
        assert all(m["value"] > 0 for m in line["metrics"].values())


def test_a_broken_timed_path_comes_out_not_correct(capsys, jax_config_restored):
    """The harness's look for a chip skipped (--rehearse), the rest of a
    run driven, with one answer altered where it is produced."""
    class Broken:
        def __init__(self, inner):
            self.inner = inner

        def execute(self, request):
            reply = self.inner.execute(request)
            if request["idx"] == 1 and reply.error is None:
                col = reply.data.column_array("w")
                col[len(col) // 2] += 1
            return reply

        def close(self):
            self.inner.close()

    rc, line, out = _rehearse(capsys, "--workload", "snb-sf100-proxy.go3", "--seed", "17",
                              "--trace", "0", wrap_session=Broken)
    assert rc == 0 and line["rehearsal"]["checks_passed"] is False
    assert "DIFFERS request 1" in out and line["failed"] == 0

    class Short(Broken):
        def execute(self, request):
            reply = self.inner.execute(request)
            if request["idx"] == 2:
                reply.n_rows -= 1                         # a wrong count is a failed operation
            return reply

    rc, line, out = _rehearse(capsys, "--workload", "snb-sf100-proxy.go3", "--seed", "17",
                              "--trace", "0", wrap_session=Short)
    assert line["failed"] > 0 and line["rehearsal"]["checks_passed"] is False


def test_without_rehearse_a_platform_other_than_tpu_is_refused(capsys):
    rc = bench_run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"])
    cap = capsys.readouterr()
    assert rc == 2 and cap.out.strip() == "" and "TPU" in cap.err


def test_a_later_pr_adds_its_pieces_as_files_only(tmp_path):
    """A temporary copy of the benchmark + a throw-away layer metric, mix,
    generator, configuration and control, and a cell naming them: run.py
    picks them up with no file of the benchmark edited."""
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = tmp_path / "benchmarks"
    mix = loader.data("traffic", "go3-single")
    mix.update(requests=3, templates=[dict(mix["templates"][0], name="go2", steps=2)])
    (bench / "traffic" / "throwaway-mix.json").write_text(json.dumps(mix))
    (bench / "layers" / "throwaway.rows_per_stmt.py").write_text(
        "def read(ctx):\n    return sum(r.n_rows for r in ctx['window']) / len(ctx['window'])\n")
    (bench / "reference" / "generators" / "throwaway_gen.py").write_text(
        "from benchmarks.reference.generators import social_arrays\n\n\n"
        "def generate(sizes, seed):\n"
        "    print('throwaway_gen made the data')\n"
        "    return social_arrays.generate(sizes, seed + 1)\n")
    (bench / "controls" / "zero_w.py").write_text(
        "from benchmarks.lib.reply import Columns\n\n\n"
        "def broken(want):\n    return Columns({**want, 'w': want['w'] * 0})\n")
    cfg = loader.data("configs", "snb-sf100-proxy")
    cfg["name"], cfg["reference"]["generator"] = "throwaway-config", "throwaway_gen"
    (bench / "configs" / "throwaway-config.json").write_text(json.dumps(cfg))
    m = json.loads(json.dumps(MANIFEST))
    m["configs"].append(dict(m["configs"][1], name="throwaway-config",
                             file="benchmarks/configs/throwaway-config.json"))
    m["workloads"].append({"name": "proxy.throwaway", "config": "throwaway-config",
                           "traffic": "throwaway-mix", "chips": 1, "why": "test"})
    m["per_layer"].append({"name": "throwaway.rows_per_stmt", "unit": "rows", "better": "higher",
                           "source": "program_counter", "layer": "test", "moves": "stmts_per_s",
                           "workloads": ["proxy.throwaway"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    p = subprocess.run([sys.executable, str(bench / "run.py"), "--workload",
                        "proxy.throwaway", "--seed", "5", "--seconds", "1", "--trace", "1",
                        "--rehearse", "--control", "zero_w"], capture_output=True, text=True,
                       env=env, timeout=300, cwd=tmp_path)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["rehearsal"]["checks_passed"] is True and line["attempted"] >= 3
    assert line["metrics"]["throwaway.rows_per_stmt"]["value"] > 0
    assert "traffic throwaway-mix" in p.stdout and "throwaway_gen made the data" in p.stdout
    assert line["control"]["name"] == "zero_w" and line["control"]["correct"] is False
