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
from benchmarks.lib.requests import make_requests, op_module  # noqa: E402
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


def test_the_recorded_trace_reads_what_it_read_before_the_spans_were_collected():
    """The numbers PR 23's reduction gave for the same file (it holds no
    program span, so its gaps keep their labels too)."""
    loaded = T.load(os.path.join(ROOT, "benchmarks", "testdata", "cpu_slice.xplane.pb"))
    r = T.reduce(loaded)
    assert (r["busy_s"], r["window_s"]) == (0.001406476, 0.032598752)
    assert r["idle_share"] == 95.68549127279475
    assert r["device_ops"] == [["dot_general.1", 0.001279219], ["wrapped_reduce-window", 0.000123641],
                               ["wrapped_reduce", 3.616e-06]]
    assert loaded["spans"] == []
    assert r["idle_gaps"] == [
        ["inside a statement, after its last device operation", 0.010492631],
        ["inside a statement, after its last device operation", 0.010373694],
        ["inside a statement, after its last device operation", 0.010183782],
        ["inside a statement, before its first device operation", 0.000139639],
        ["inside a statement, between device operations", 8.31e-07]]


def test_a_reader_gets_each_operations_seconds_on_each_plane_from_the_events_alone():
    """What `ctx["events"]` is for: a per-layer reader of a later PR (the
    exchange collective on each chip of a mesh) sums one named operation
    per device plane, whether or not it is among the ten longest."""
    events = T.load(os.path.join(ROOT, "benchmarks", "testdata", "cpu_slice.xplane.pb"))
    t0, t1 = T.window(events)
    per_plane = {plane: sum(min(e, t1) - max(s, t0) for name, s, e in ops
                            if name.startswith("wrapped_reduce-window") and e > t0 and s < t1) / 1e9
                 for plane, ops in events["devices"].items()}
    assert per_plane == {"/host:CPU (hlo_op events)": pytest.approx(0.000123641)}
    hand = {"devices": {"/device:TPU:0": [("all-to-all.1", _ns(10), _ns(14)), ("fusion", _ns(14), _ns(30))],
                        "/device:TPU:1": [("all-to-all.1", _ns(10), _ns(22)), ("fusion", _ns(22), _ns(30))]},
            "spans": [], "marks": {T.SLICE_BEGIN: [], T.SLICE_END: [], T.STMT: []}}
    assert T.window(hand) == (_ns(10), _ns(30))           # no marks: first operation to last
    assert {p: sum(e - s for n, s, e in ops if n.startswith("all-to-all")) / 1e6
            for p, ops in hand["devices"].items()} == {"/device:TPU:0": 4.0, "/device:TPU:1": 12.0}
    assert T.reduce(hand)["busy_s"] == pytest.approx(0.020)


def test_idle_gaps_name_the_phases_of_the_innermost_open_spans():
    """Two thread lines of nested program spans: the leaf is counted, not
    its parents; a gap with no span open keeps the harness's label."""
    dev = [("a", _ns(6), _ns(10)), ("a", _ns(40), _ns(50)), ("a", _ns(70), _ns(80)),
           ("a", _ns(95), _ns(100))]
    spans = [("query:Go", "python#1", _ns(5), _ns(68)),
             ("exec:TpuTraverse", "python#1", _ns(6), _ns(66)),
             ("tpu:snapshot_check", "python#1", _ns(12), _ns(38)),
             ("storage:storage.part_stats", "python#2", _ns(13), _ns(37)),
             ("rpc:storage.part_stats", "python#2", _ns(14), _ns(36)),
             ("rpc.server:storage.part_stats", "python#3", _ns(20), _ns(30)),
             ("device:fetch", "python#1", _ns(51), _ns(64)),
             ("graphd:encode", "python#1", _ns(66), _ns(67))]
    loaded = {"devices": {"/device:TPU:0": dev}, "spans": spans,
              "marks": {T.SLICE_BEGIN: [(0, 0)], T.SLICE_END: [(_ns(100), _ns(100))],
                        T.STMT: [(_ns(4), _ns(69))]}}
    gaps = dict((round(d * 1e3), label) for label, d in T.reduce(loaded)["idle_gaps"])
    assert gaps == {
        30: "1xsnapshot_check 1xrpc_wait 1xremote; inside a statement, between device operations",
        20: "1xfetch; inside a statement, after its last device operation",
        15: "between statements",                         # [80, 95): no span open
        6: "between statements"}                          # [0, 6)
    leaves = sorted(n for n, *_ in T.leaves_at(spans, _ns(25)))
    assert leaves == ["rpc.server:storage.part_stats", "rpc:storage.part_stats", "tpu:snapshot_check"]
    assert T.leaves_at(spans, _ns(67.5)) == [("query:Go", "python#1", _ns(5), _ns(68))]
    assert T.leaves_at(spans, _ns(90)) == []


@pytest.mark.parametrize("name,span,phase", [
    ("query:Go", True, "other"), ("graphd:parse", True, "parse"), ("graphd:plan", True, "plan"),
    ("graphd:admit", True, "admit"), ("graphd:encode", True, "encode"),
    ("exec:TpuTraverse", True, "exec"), ("tpu:pin", True, "exec"), ("store:scan", True, "exec"),
    ("raft:append", True, "exec"), ("tpu:snapshot_check", True, "snapshot_check"),
    ("storage:storage.part_stats", True, "rpc_wait"), ("rpc:meta.update_session", True, "rpc_wait"),
    ("meta:heartbeat", True, "rpc_wait"), ("rpc.server:storage.part_stats", True, "remote"),
    ("device:queue", True, "queue"), ("device:put", True, "put"), ("device:dispatch", True, "dispatch"),
    ("device:fetch", True, "fetch"), ("device:materialise", True, "materialise"),
    ("rpc:retry", True, None), ("storage:dedup_hit", True, None),    # zero-length markers
    # a layer no list here ever held (what a later program PR opens): a span by
    # its shape, in the phase the program's own map gives what it does not name
    ("compact:rebuild", True, "exec"), ("delta.compact:swap", True, "exec"),
    ("wal2:fsync_wait", True, "exec"),
    # what else the profiler writes on the host plane (the recorded trace's, a chip's)
    ("tpu::System::Execute", False, None), ("bench:stmt", False, None),
    ("bench:slice_begin", False, None), ("end: dot_general.1", False, None),
    ("PjitFunction(<lambda>)", False, None), ("np.asarray(jax.Array)", False, None),
    ("ThunkExecutor::Execute (wait for completion)", False, None),
    ("ThreadpoolListener::StartRegion", False, None), ("Compact:rebuild", False, None),
    ("compact rebuild: part 3", False, None), ("compact:", False, None),
    ("PjRtCpuExecutable::Execute", False, None), ("%fusion.16 = s32[8] fusion(...)", False, None)])
def test_which_host_events_are_program_spans_and_their_phase(name, span, phase):
    from nebula_tpu.utils import trace as program
    assert T.is_span(name) is span
    if span:
        assert T.phase_of(name) == phase and (phase is None or phase in T.PHASES)
        # the program's own map and vocabulary, not a copy of them: but for a
        # handler's span, a gap's label is what the `graphd.*` counters book
        assert T.PHASES is program.PHASES
        assert phase == "remote" or T.phase_of(name) == program.phase_of(name)


def test_a_span_of_a_layer_no_list_held_is_loaded_and_names_the_gap_it_is_open_in(tmp_path):
    """A trace recorded here, as run.py records its slice: the program's
    own `span()` under a `query:` root while a profiler session collects,
    under a layer (`compact:`) that the parent's `SPAN_PREFIXES` lacked.
    `load()` keeps it with the root, and the gap it is open in reads its
    phase."""
    import time

    import jax
    import jax.numpy as jnp

    from nebula_tpu.utils import trace as program

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((128, 128), jnp.float32)
    f(x).block_until_ready()
    prof = jax.profiler
    opts = prof.ProfileOptions()
    opts.python_tracer_level = 0
    prof.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with prof.TraceAnnotation(T.SLICE_BEGIN):
            pass
        with prof.TraceAnnotation(T.STMT, idx=0), program.start_trace("query:Go"):
            f(x).block_until_ready()
            with program.span("compact:rebuild"):
                time.sleep(0.05)
            f(x).block_until_ready()
        with prof.TraceAnnotation(T.SLICE_END):
            pass
    finally:
        prof.stop_trace()
    loaded = T.load(T.find_xplane(str(tmp_path)))
    assert sorted(n for n, *_ in loaded["spans"]) == ["compact:rebuild", "query:Go"]
    label, seconds = T.reduce(loaded)["idle_gaps"][0]
    assert label == "1xexec; inside a statement, between device operations" and seconds >= 0.05


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
    if control in ("f32", "nan_f"):                       # (a later PR's control may be of their kind)
        assert b is None                                  # nothing to break without a double
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
    cap = capsys.readouterr()
    line = json.loads(cap.out.strip().splitlines()[-1])
    # each number compared beside its limit: last in the line, and the last lines of stderr
    assert list(line)[-1] == "checks" and "rows_mismatched" in line["checks"]
    assert cap.err.strip().splitlines()[-len(line["checks"]):] == [
        f"check {k}: {c['value']:g} (limit {c['limit']:g})" for k, c in line["checks"].items()]
    return rc, line, cap.out


def rehearsal_control(cell, trace):
    """The control a cell's rehearsal runs beside its check: the one its
    traffic mix names (`rehearsal_controls`: [untraced, traced]); for a
    mix that names none, `bad_row` and `drop_row`, which any table of
    rows can suffer, and where the cell's answers give that one nothing
    to break (`broken(want)` is None for every request), the first file
    of controls/ that finds something."""
    w = next(x for x in MANIFEST["workloads"] if x["name"] == cell)
    cfg, mix = loader.data("configs", w["config"]), loader.data("traffic", w["traffic"])
    if "rehearsal_controls" in mix:
        return mix["rehearsal_controls"][trace]
    sizes = cfg["rehearse"]
    mix["requests"] = min(int(mix["requests"]), int(sizes.get("requests", mix["requests"])))
    ref = RefGraph(generate(cfg["reference"]["generator"], sizes, BIG_SEED + trace),
                   cfg["reference"]["dedupe_last"])
    wants = [op_module(r["template"]["op"]).answer(ref, r["template"], r["start"])
             for r in make_requests(mix, ref, BIG_SEED + trace)]
    return next(c for c in [("bad_row", "drop_row")[trace]] + CONTROLS
                if any(broken(c, want) is not None for want in wants))


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_every_cell_has_a_control_for_its_rehearsal(cell, trace):
    assert rehearsal_control(cell, trace) in CONTROLS


def test_a_mix_that_names_no_control_gets_one_that_breaks_its_answers(monkeypatch):
    data = loader.data

    def unnamed(kind, name):
        d = data(kind, name)
        d.pop("rehearsal_controls", None)
        return d
    monkeypatch.setattr(loader, "data", unnamed)
    assert [rehearsal_control("snb-sf100-proxy.go3", t) for t in (0, 1)] == ["bad_row", "drop_row"]
    assert [rehearsal_control("snb-sf1.path-1s", t) for t in (0, 1)] == ["bad_row", "drop_row"]
    # answers with no table in them (paths only): the defaults find nothing, nor does any file
    monkeypatch.setattr(sys.modules[__name__], "broken", lambda c, want: None)
    with pytest.raises(StopIteration):
        rehearsal_control("snb-sf1.path-1s", 0)
    # ... and where only one control of the directory breaks them, that one is found
    monkeypatch.setattr(sys.modules[__name__], "broken",
                        lambda c, want: want if c == CONTROLS[-1] else None)
    assert rehearsal_control("snb-sf1.path-1s", 1) == CONTROLS[-1]


def _counters_moved(run):
    """-> (what `run()` returns, {program counter: by how much it moved
    in the whole of it})."""
    from nebula_tpu.utils.stats import stats
    c0 = stats().snapshot()
    got = run()
    c1 = stats().snapshot()
    return got, {k: v - c0.get(k, 0) for k, v in c1.items()
                 if isinstance(v, (int, float)) and v != c0.get(k, 0)}


def may_be_left_out(kind, name, moved):
    """A rehearsal has to print every listed metric, but for one whose
    reader names the counters it reads (`NEEDS`), in a run in which none
    of them moved: the tiny sizes loop no hop, say."""
    needs = getattr(loader.module(kind, name), "NEEDS", ())
    return bool(needs) and not any(moved.get(k) for k in needs)


def test_only_a_reader_whose_counters_stayed_may_leave_its_metric_out():
    assert may_be_left_out("layers", "kernel.chunk_share", {"tpu_kernel_runs": 7})
    assert not may_be_left_out("layers", "kernel.chunk_share", {"tpu_hop_chunks_budget": 32})
    assert not may_be_left_out("layers", "dispatch.put_ms", {})       # names none: always printed
    assert not may_be_left_out("end_to_end", "stmt_p50_ms", {})


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_each_cell(cell, trace, capsys, jax_config_restored):
    (rc, line, out), moved = _counters_moved(lambda: _rehearse(
        capsys, "--workload", cell, "--seed", str(BIG_SEED + trace),
        "--trace", str(trace), "--control", rehearsal_control(cell, trace)))
    assert rc == 0
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is False                       # a rehearsal is never a chip result
    assert line["rehearsal"]["checks_passed"] is True, out[-3000:]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    assert line["control"]["correct"] is False            # the check refuses the control
    group, kind = ("per_layer", "layers") if trace else ("end_to_end", "end_to_end")
    want = {m["name"] for m in bench_run.metrics_for(MANIFEST, group, cell)
            if m["source"] != "device_trace"}
    for name in want - set(line["metrics"]):
        assert may_be_left_out(kind, name, moved), (name, line["metrics"])
    for m in line["metrics"].values():
        assert isinstance(m["value"], (int, float)) and m["unit"]
    if trace:
        assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > line["device"]["busy_s"]
        assert len(line["breakdown"]["device_ops"]) <= 10 and len(line["breakdown"]["idle_gaps"]) <= 10
        assert "device.idle_share" in line["rehearsal"]["cpu_backend_readings"]
        assert "device.idle_share" not in line["metrics"]  # no CPU number under a device metric
    else:
        assert all(m["value"] > 0 for m in line["metrics"].values())


def test_a_rehearsal_whose_hops_loop_prints_the_chunk_share(capsys, jax_config_restored, monkeypatch):
    """The configurations' own rehearsal sizes fit every hop's budget into
    one chunk (straight-line: the counters stay, nothing to read).  At
    6,000 persons x 64 the proxy cell's last hop holds four chunks a part
    and fills three."""
    data = loader.data

    def larger(kind, name):
        d = data(kind, name)
        if (kind, name) == ("configs", "snb-sf100-proxy"):
            d["rehearse"].update(persons=6000, degree=64)
        return d
    monkeypatch.setattr(loader, "data", larger)
    (rc, line, out), moved = _counters_moved(lambda: _rehearse(
        capsys, "--workload", "snb-sf100-proxy.go3", "--seed", "26", "--trace", "1"))
    assert rc == 0 and line["rehearsal"]["checks_passed"] is True, out[-3000:]
    assert moved["tpu_hop_chunks_budget"] >= moved["tpu_hop_chunks_run"] > 0
    assert 0 < line["metrics"]["kernel.chunk_share"]["value"] <= 100


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


# What a later PR's test run keeps of this directory's tests when it asks
# for the STRUCTURAL ones: every test that takes no fixture.  None of
# those drives a run (a rehearsal captures its line, `capsys`; a builder's
# deployment is a fixture; a copy is made under `tmp_path`), and every
# check of the manifest, the loader and the pieces' files is among them.
# By what a test takes, not by its name: the next PR's test file is in
# the selection with no line of this one changed.
NO_FIXTURE_PLUGIN = """
def pytest_collection_modifyitems(config, items):
    keep, drop = [], []
    for it in items:
        params = set(it.callspec.params) if hasattr(it, "callspec") else set()
        (drop if set(it.fixturenames) - params else keep).append(it)
    items[:] = keep
    config.hook.pytest_deselected(items=drop)
"""


@pytest.fixture(scope="module")
def later_pr(tmp_path_factory):
    """A temporary copy of the benchmark and of its tests + what a later
    program PR would bring, with no file of the copy edited: a throw-away
    layer metric at the END of `per_layer`, a mix, a generator, a
    configuration with its file and a control, a cell naming them, and a
    second cell of that configuration under a mix that is there.
    -> (the copy's root, its manifest, the environment its runs take)."""
    root = tmp_path_factory.mktemp("later_pr")
    bench = root / "benchmarks"
    shutil.copytree(os.path.join(ROOT, "benchmarks"), bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(os.path.join(ROOT, "tests", "benchmark"), root / "tests" / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    mix = loader.data("traffic", "go3-single")
    mix.update(requests=3, templates=[dict(mix["templates"][0], name="go2", steps=2)])
    del mix["rehearsal_controls"]                         # a later PR's mix need not name any
    (bench / "traffic" / "throwaway-mix.json").write_text(json.dumps(mix))
    (bench / "layers" / "throwaway.rows_per_stmt.py").write_text(
        "def read(ctx):\n    return sum(r.n_rows for r in ctx['window']) / len(ctx['window'])\n")
    (bench / "reference" / "generators" / "throwaway_gen.py").write_text(
        "from benchmarks.reference.generators import social_arrays\n\n\n"
        "def generate(sizes, seed):\n"
        "    print('throwaway_gen made the data')\n"
        "    return social_arrays.generate(sizes, seed + 1)\n")
    (bench / "controls" / "zero_w.py").write_text(
        "from benchmarks.lib.reply import Columns, columns_of\n\n\n"
        "def broken(want):\n    cols = columns_of(want)\n"
        "    if cols is None or 'w' not in cols:\n        return None\n"
        "    return Columns({**cols, 'w': cols['w'] * 0})\n")
    cfg = loader.data("configs", "snb-sf100-proxy")
    cfg["name"], cfg["reference"]["generator"] = "throwaway-config", "throwaway_gen"
    (bench / "configs" / "throwaway-config.json").write_text(json.dumps(cfg))
    m = json.loads(json.dumps(MANIFEST))
    m["configs"].append(dict(m["configs"][1], name="throwaway-config",
                             file="benchmarks/configs/throwaway-config.json"))
    m["workloads"].append({"name": "proxy.throwaway", "config": "throwaway-config",
                           "traffic": "throwaway-mix", "chips": 1, "why": "test"})
    # ... and a second cell, of the new configuration under a mix that is there:
    # nothing below counts on how many cells the manifest holds
    m["workloads"].append({"name": "proxy.throwaway-2", "config": "throwaway-config",
                           "traffic": "go3-single", "chips": 1, "why": "test"})
    m["per_layer"].append({"name": "throwaway.rows_per_stmt", "unit": "rows", "better": "higher",
                           "source": "program_counter", "layer": "test", "moves": "stmts_per_s",
                           "workloads": ["proxy.throwaway"]})
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    (root / "no_fixture.py").write_text(NO_FIXTURE_PLUGIN)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join([str(root), ROOT]),
               JAX_COMPILATION_CACHE_DIR=str(root / "cache"))
    return root, m, env


def test_a_later_pr_adds_its_pieces_as_files_only(later_pr):
    """run.py, started in the copy, picks the new cell's pieces up by the
    names the copy's data gives them."""
    root, m, env = later_pr
    p = subprocess.run([sys.executable, str(root / "benchmarks" / "run.py"), "--workload",
                        "proxy.throwaway", "--seed", "5", "--seconds", "1", "--trace", "1",
                        "--rehearse", "--control", "zero_w"], capture_output=True, text=True,
                       env=env, timeout=300, cwd=root)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["rehearsal"]["checks_passed"] is True and line["attempted"] >= 3
    assert line["metrics"]["throwaway.rows_per_stmt"]["value"] > 0
    assert "traffic throwaway-mix" in p.stdout and "throwaway_gen made the data" in p.stdout
    assert line["control"]["name"] == "zero_w" and line["control"]["correct"] is False


def test_every_structural_test_of_the_suite_passes_with_a_later_prs_entries_appended(later_pr):
    """The benchmark's own tests, run in the copy (their ROOT is the copy):
    EVERY test of every file there that takes no fixture, so each check
    this suite applies to the manifest, to the loader, to `metrics_for`
    and to the pieces' files holds with the new entries in it — the
    metric at the END of `per_layer`, the cells and the configuration
    after those that are there.  A test that pins a position or a length
    of a list of the manifest fails here (`test_write_read.py` held the
    last six entries of `per_layer` until PR 36, and no PR but a
    `benchmark` one may edit that file).  Counted by what the copy's
    manifest holds, never by a number written here: the next PR's cell,
    or its test file, changes no line of this."""
    root, m, env = later_pr
    p = subprocess.run([sys.executable, "-m", "pytest", "tests/benchmark", "-q", "-rA", "-p",
                        "no:cacheprovider", "-p", "no_fixture"],
                       capture_output=True, text=True, env=env, timeout=300, cwd=root)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-2000:]
    passed = set(re.findall(r"^PASSED \S+::(\S+)$", p.stdout, re.M))
    assert not re.search(r"^(FAILED|ERROR) ", p.stdout, re.M), p.stdout[-2000:]
    # the selection holds the tests that read the manifest, in every file that has one
    assert {"test_manifest_has_exactly_the_contract_keys", "test_the_cell_is_the_issues",
            "test_names_units_and_lines_use_the_allowed_characters",
            "test_every_layer_metric_moves_a_metric_its_cells_report",
            "test_the_manifest_names_the_fifteen_new_metrics"} <= passed
    # ... and none that would copy the copy again or drive a run
    assert not any("later_pr" in t or "rehearsal_of_each_cell" in t for t in passed)
    for w in m["workloads"]:                              # those that are there and the two new
        assert f"test_every_piece_a_cell_names_is_a_file_that_exists[{w['name']}]" in passed
        for trace in (0, 1):
            assert f"test_every_cell_has_a_control_for_its_rehearsal[{trace}-{w['name']}]" in passed
    assert len(m["workloads"]) == len(CELLS) + 2 and len(passed) >= 4 + 3 * len(m["workloads"])
    # `metrics_for` over the copy's manifest: the new metric in its cell alone, and
    # every cell that was there reports what it reported
    new = [x["name"] for x in bench_run.metrics_for(m, "per_layer", "proxy.throwaway")]
    assert new[-1] == "throwaway.rows_per_stmt"
    for cell in CELLS + ["proxy.throwaway-2"]:
        now = [x["name"] for x in bench_run.metrics_for(m, "per_layer", cell)]
        assert "throwaway.rows_per_stmt" not in now
        assert cell not in CELLS or now == [
            x["name"] for x in bench_run.metrics_for(MANIFEST, "per_layer", cell)]
