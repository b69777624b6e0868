"""Tier-1 tests of what `graphalytics-dg75-proxy.pr-wcc-sssp` brings to
the benchmark (PR 47): the three whole-graph reference operations on
hand-worked graphs (a dangling vertex, two components, a zero-weight edge,
an unreached vertex), their comparisons and the two controls they refuse,
the generator's one weight a friendship, the statement texts parsed by the
repo's parser and held to what the builder passes, the `prebuilt_algo`
builder on one device among tier-1's eight with every new span, series and
the gauge moving, and an untraced and a traced rehearsal of the cell.  The
cell's plain rehearsals, control look-ups and pieces test are
test_benchmark.py's parametrised cases; the five readers are
test_algo_metrics.py's."""
from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.lib import loader  # noqa: E402
from benchmarks.lib.reply import Columns, Reply  # noqa: E402
from benchmarks.lib.requests import make_requests  # noqa: E402
from benchmarks.reference.graph import RefGraph  # noqa: E402

from test_phase_metrics import jax_config_restored  # noqa: E402,F401

CELL, CONFIG, MIX = ("graphalytics-dg75-proxy.pr-wcc-sssp", "graphalytics-dg75-proxy",
                     "algo3-single")
MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CFG = loader.data("configs", CONFIG)
TEMPLATES = {t["name"]: t for t in loader.data("traffic", MIX)["templates"]}
PR, WCC, SSSP = (loader.module("reference/ops", op) for op in ("pagerank", "wcc", "sssp"))
SERIES = ("algo_prepare_s", "algo_put_s", "algo_assemble_s")


def graph(n, pairs):
    """`pairs` of (a, b, weight) as both rows a -> b and b -> a."""
    a, b, w = (np.asarray(c) for c in zip(*pairs))
    f = np.concatenate([w, w]).astype(np.float64)
    return RefGraph({"n": n, "edges": {"KNOWS": {
        "src": np.concatenate([a, b]), "dst": np.concatenate([b, a]),
        "w": np.zeros(f.size, np.int64), "f": f}}})


def hand_graph():
    """0 - 1 (1.0), 1 - 2 (0.0), 0 - 2 (2.5), 2 - 3 (0.5); 4 - 5 (1.0) apart;
    6 has no row at all."""
    return graph(7, [(0, 1, 1.0), (1, 2, 0.0), (0, 2, 2.5), (2, 3, 0.5), (4, 5, 1.0)])


def reply(cols):
    return Reply(n_rows=int(next(iter(cols.values())).size), data=Columns(cols))


# ---------------------------------------------------------------------------
# the reference operations
# ---------------------------------------------------------------------------


def test_pagerank_of_a_hand_worked_graph_spreads_the_dangling_mass_evenly():
    g = hand_graph()
    one = dict(TEMPLATES["pagerank"], params=dict(TEMPLATES["pagerank"]["params"], max_iter=1))
    want = PR.answer(g, one, 0)
    # out-degrees 2, 2, 3, 1, 1, 1, 0; everyone starts at 1/7 and vertex 6 is dangling
    r, d = 1 / 7, 0.85
    base = (1 - d) / 7 + d * r / 7
    by_hand = [base + d * (r / 2 + r / 3), base + d * (r / 2 + r / 3),
               base + d * (r / 2 + r / 2 + r), base + d * r / 3, base + d * r, base + d * r, base]
    assert want["vid"].tolist() == list(range(7))
    assert want["rank"] == pytest.approx(by_hand, rel=1e-15)
    ten = PR.answer(g, TEMPLATES["pagerank"], 3)["rank"]       # the start means nothing
    assert ten.sum() == pytest.approx(1.0, rel=1e-14) and ten.dtype == np.float64
    assert ten[6] == ten.min() and ten[2] == ten.max() and ten[4] == ten[5]
    assert PR.count(g, TEMPLATES["pagerank"], 0) == 7
    assert PR.profile(TEMPLATES["pagerank"], 0) == {
        "algo": "pagerank", "iterations": 10, "rows": 10, "vertices": 7}


def test_pagerank_reads_the_rows_as_directed():
    # 0 -> 1 only: 1 is dangling, and nothing flows back but its spread mass
    g = RefGraph({"n": 2, "edges": {"KNOWS": {"src": np.array([0]), "dst": np.array([1]),
                                              "w": np.zeros(1), "f": np.ones(1)}}})
    one = dict(TEMPLATES["pagerank"], params=dict(TEMPLATES["pagerank"]["params"], max_iter=1))
    assert PR.answer(g, one, 0)["rank"] == pytest.approx(
        [0.075 + 0.85 * 0.25, 0.075 + 0.85 * (0.5 + 0.25)])


def test_wcc_of_a_hand_worked_graph_names_a_component_by_its_smallest_vid():
    g = hand_graph()
    want = WCC.answer(g, TEMPLATES["wcc"], 0)
    assert want["component"].tolist() == [0, 0, 0, 0, 4, 4, 6]
    assert WCC.count(g, TEMPLATES["wcc"], 5) == 7
    assert WCC.profile(TEMPLATES["wcc"], 0) == {"algo": "wcc", "rows": 10, "vertices": 7}
    # a row is read both ways: one directed row joins its ends
    one_way = RefGraph({"n": 3, "edges": {"KNOWS": {"src": np.array([2]), "dst": np.array([1]),
                                                    "w": np.zeros(1), "f": np.ones(1)}}})
    assert WCC.answer(one_way, TEMPLATES["wcc"], 0)["component"].tolist() == [0, 1, 1]
    # a long path converges (pointer jumping) and takes its smallest end's vid
    path = graph(64, [(i, i + 1, 1.0) for i in range(1, 63)])
    assert WCC.answer(path, TEMPLATES["wcc"], 0)["component"].tolist() == [0] + [1] * 63


def test_sssp_of_a_hand_worked_graph_takes_the_zero_weight_edge_and_leaves_out_the_unreached():
    g = hand_graph()
    want = SSSP.answer(g, TEMPLATES["sssp"], 0)
    # 0 - 2 costs 1.0 over 1 (the zero-weight edge), not 2.5; 4, 5, 6 are out of reach
    assert want["vid"].tolist() == [0, 1, 2, 3]
    assert want["distance"].tolist() == [0.0, 1.0, 1.0, 1.5]
    assert SSSP.count(g, TEMPLATES["sssp"], 0) == 4 and SSSP.count(g, TEMPLATES["sssp"], 5) == 2
    assert SSSP.count(g, TEMPLATES["sssp"], 6) == 1
    alone = SSSP.answer(g, TEMPLATES["sssp"], 6)
    assert (alone["vid"].tolist(), alone["distance"].tolist()) == ([6], [0.0])
    # the rows out of the vertices 0 reaches: 2 + 2 + 3 + 1
    assert SSSP.profile(TEMPLATES["sssp"], 0) == {"algo": "sssp", "rows": 8, "vertices": 7}
    assert SSSP.profile(TEMPLATES["sssp"], 4) is None            # never asked for
    # rows are followed in their direction
    one_way = RefGraph({"n": 3, "edges": {"KNOWS": {
        "src": np.array([0, 1]), "dst": np.array([1, 2]), "w": np.zeros(2),
        "f": np.array([0.25, 0.5])}}})
    assert SSSP.answer(one_way, TEMPLATES["sssp"], 0)["distance"].tolist() == [0.0, 0.25, 0.75]
    assert SSSP.answer(one_way, TEMPLATES["sssp"], 2)["vid"].tolist() == [2]
    assert PR.profile(TEMPLATES["pagerank"], 0)["vertices"] == 3  # the graph last seen


def test_a_second_graph_empties_what_the_operations_keep():
    g = hand_graph()
    SSSP.answer(g, TEMPLATES["sssp"], 0)
    other = hand_graph()
    assert SSSP.count(other, TEMPLATES["sssp"], 4) == 2
    assert SSSP.profile(TEMPLATES["sssp"], 0) is None


# ---------------------------------------------------------------------------
# the comparisons and the controls
# ---------------------------------------------------------------------------


def test_values_are_compared_vertex_by_vertex_with_a_gap_for_a_double():
    g = hand_graph()
    want = SSSP.answer(g, TEMPLATES["sssp"], 0)
    assert SSSP.compare(reply(want), want)[:2] == (0, 0.0)
    shuffled = {k: v[::-1].copy() for k, v in want.items()}     # the order of the rows is free
    assert SSSP.compare(reply(shuffled), want)[:2] == (0, 0.0)
    off = dict(want, distance=want["distance"] * (1 + 3e-9))
    bad, gap, _ = SSSP.compare(reply(off), want)
    assert bad == 0 and gap == pytest.approx(3e-9, rel=1e-3) and gap > CFG["limits"]["float_rel_gap"]
    # a vertex missing, one too many, one twice
    assert SSSP.compare(reply({k: v[:-1] for k, v in want.items()}), want)[0] == 1
    extra = {"vid": np.append(want["vid"], 6), "distance": np.append(want["distance"], 9.0)}
    assert SSSP.compare(reply(extra), want)[0] == 1
    twice = {"vid": np.array([0, 1, 2, 2]), "distance": want["distance"]}
    assert SSSP.compare(reply(twice), want)[0] >= 1
    # an infinity where the reference has a number is a row that differs, and no gap
    lost = dict(want, distance=np.array([0.0, 1.0, np.inf, 1.5]))
    assert SSSP.compare(reply(lost), want)[:2] == (1, 0.0)
    # a double has to arrive as float64
    narrow = dict(want, distance=want["distance"].astype(np.float32))
    assert SSSP.compare(reply(narrow), want)[0] == 4


def test_wcc_is_compared_by_partition_and_then_by_label():
    g = hand_graph()
    want = WCC.answer(g, TEMPLATES["wcc"], 0)
    assert WCC.compare(reply(want), want)[0] == 0
    # the same partition under other labels: nobody in another class, everybody relabelled
    renamed = dict(want, component=np.array([3, 3, 3, 3, 5, 5, 6]))
    bad, _, detail = WCC.compare(reply(renamed), want)
    assert bad == 6 and "0 in another class, 6 under another label" in detail
    # two components merged under a sound label: all six of them differ
    merged = dict(want, component=np.array([0, 0, 0, 0, 0, 0, 6]))
    assert WCC.compare(reply(merged), want)[0] == 6
    assert WCC.compare(reply({k: v[1:] for k, v in want.items()}), want)[0] == 1


@pytest.mark.parametrize("control", ["f32", "wcc_split"])
def test_both_controls_are_refused(control):
    g = graph(5, [(0, 1, 0.1), (1, 2, 0.2), (3, 4, 0.7)])
    broken = loader.module("controls", control).broken
    limit = CFG["limits"]["float_rel_gap"]
    refused = 0
    for op, t in ((PR, TEMPLATES["pagerank"]), (WCC, TEMPLATES["wcc"]), (SSSP, TEMPLATES["sssp"])):
        want = op.answer(g, t, 0)
        b = broken(want)
        if b is None:
            assert control == "f32" and op is WCC                 # no double to narrow
            continue
        bad, gap, _ = op.compare(b, want)
        assert bad >= 1 or gap > limit, (control, t["name"], bad, gap)
        refused += 1
        if control == "f32":
            assert bad == 0 and limit < gap < 1e-6                # float32 rounding, far over 1e-9
    assert refused == (2 if control == "f32" else 3)
    if control == "wcc_split":
        want = WCC.answer(g, TEMPLATES["wcc"], 0)
        got = broken(want).column("component")
        # one vertex under its own vid: a sound label for a component of one
        (at,) = np.flatnonzero(got != want["component"])
        assert got[at] == want["vid"][at] != want["component"][at]
        bad, _, detail = WCC.compare(broken(want), want)
        assert bad == np.sum(want["component"] == want["component"][at]) and "0 under" in detail
        # every component of one vertex: nothing to move out, an integer is moved in its place
        alone = {"vid": np.arange(3), "component": np.arange(3)}
        assert WCC.compare(broken(alone), alone)[0] >= 1


# ---------------------------------------------------------------------------
# the generator, the manifest, the statements
# ---------------------------------------------------------------------------


def test_the_two_rows_of_a_friendship_carry_one_weight():
    gen = loader.module("reference/generators", CFG["reference"]["generator"])
    seed = 2 ** 31 + 4747
    t = gen.generate(CFG["rehearse"], seed)
    e = t["edges"]["KNOWS"]
    half = e["src"].size // 2
    assert np.array_equal(e["src"][:half], e["dst"][half:])
    assert np.array_equal(e["dst"][:half], e["src"][half:])
    assert np.array_equal(e["weight"][:half], e["weight"][half:])
    assert 0 < e["weight"].min() and e["weight"].max() <= 1 and e["weight"].dtype == np.float64
    assert np.unique(e["weight"]).size == half            # one draw a friendship, not one a row
    assert e["f"] is e["weight"] and not e["w"].any()
    # the pairs are knows_symmetric's own draw, and the same seed gives the same weights
    drawn = loader.module("reference/generators", "knows_symmetric").generate(CFG["rehearse"], seed)
    assert np.array_equal(drawn["edges"]["KNOWS"]["src"], e["src"])
    assert np.array_equal(gen.generate(CFG["rehearse"], seed)["edges"]["KNOWS"]["weight"], e["weight"])
    assert not np.array_equal(gen.generate(CFG["rehearse"], seed + 1)["edges"]["KNOWS"]["weight"][:9],
                              e["weight"][:9])
    assert t["n"] == CFG["rehearse"]["persons"] and RefGraph(t).n_edges("KNOWS") == 2 * half


def test_the_cell_is_the_issues():
    w = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, MIX, 1)
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    assert "datagen-7_5-fb" in entry["source"] and "2011.15028" in entry["source"]
    assert len(entry["source"]) <= 200 and entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert {"served_path", "snapshot_layout", "degree_distribution", "algorithms",
            "validation"} <= set(entry["reduced"])
    assert CFG["architecture"] is None and CFG["builder"] == "prebuilt_algo"
    assert CFG["fixes"]["schema"] == {"tags": {"Person": {}}, "edges": {"KNOWS": {"weight": "double"}}}
    s = CFG["sizes"]
    assert (s["degree"], s["max_degree"], s["parts"]) == (108, 1000, 8)
    # persons are the dataset's, or the sizing rule's cut of them with its readings on record
    assert s["persons"] == 633_432 or (
        "persons" in CFG["reduced"] and s["persons"] in (316_716, 158_358, 79_179))
    assert CFG["limits"] == {"float_rel_gap": 1e-9, "rows_mismatched": 0}
    assert {"dataset_counts", "pr_iterations", "sssp_source", "parts", "max_degree",
            "sessions"} <= set(CFG["assumed"])
    reported = {m["name"] for m in bench_run.metrics_for(MANIFEST, "end_to_end", CELL)}
    assert reported == {"stmt_p50_ms", "stmts_per_s", "setup_s"}
    mix = loader.data("traffic", MIX)
    assert (mix["driver"], mix["sessions"], mix["requests"], mix["whole_rounds"],
            mix["warmup_rounds"], mix["trace_statements"]) == ("closed_loop", 1, 3, True, 1, 3)
    assert mix["rehearsal_controls"] == ["f32", "wcc_split"]
    assert sorted(TEMPLATES) == ["pagerank", "sssp", "wcc"]


UNLISTED = ("dispatch.device_ms", "dispatch.queue_ms", "dispatch.put_ms", "dispatch.fetch_ms",
            "dispatch.retries_per_stmt", "dispatch.refetches_per_stmt",
            "dispatch.fetch_kept_share", "xla.compiles_in_window", "device.idle_share",
            "host.cpu_cores_busy")


def test_the_metrics_without_a_list_are_this_cells_too():
    """An algo statement keeps a TraverseStats and the runtime settles it
    into the series every device statement's launch settles, so the ten
    accepted metrics that list no cell read here as they stand (the traced
    rehearsal below prints them)."""
    got = {m["name"] for m in bench_run.metrics_for(MANIFEST, "per_layer", CELL)}
    for name in UNLISTED:
        m = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
        assert "workloads" not in m and name in got
    assert "dispatch.mat_ms" not in got                   # lists its cells: not this one's


@pytest.mark.parametrize("name", sorted(TEMPLATES))
def test_a_statements_text_parses_to_the_parameters_the_builder_passes(name):
    from nebula_tpu.algo import validate_call
    from nebula_tpu.core import expr as E
    from nebula_tpu.query.parser import parse
    t = TEMPLATES[name]
    s = parse(t["text"].replace("$v", "41"))
    assert (s.module, s.func) == ("algo", t["func"]) and t["op"] == t["func"] == name
    parsed = {k: e.eval(E.DictContext()) for k, e in s.params.items()}
    assert parsed == {k: 41 if v == "$v" else v for k, v in t["params"].items()}
    yields = [c.expr.name for c in s.yield_.columns]
    validate_call(s.func, list(parsed), yields)
    builder = loader.module("builders", CFG["builder"])
    assert yields == ["vid", builder.OPS[name]]
    assert "mode" not in t["params"]                      # the default, `auto`
    (et,) = t["params"]["edge_types"]
    assert et in CFG["fixes"]["schema"]["edges"]
    if name == "sssp":
        assert CFG["fixes"]["schema"]["edges"][et][t["params"]["weight"]] == "double"
    if name == "pagerank":
        assert (t["params"]["damping"], t["params"]["max_iter"], t["params"]["tol"]) == (0.85, 10, 0.0)


def test_the_request_list_holds_one_of_each_and_a_reachable_source():
    gen = loader.module("reference/generators", CFG["reference"]["generator"])
    ref = RefGraph(gen.generate(CFG["rehearse"], 2 ** 31 + 7), False)
    reqs = make_requests(loader.data("traffic", MIX), ref, 2 ** 31 + 7)
    assert sorted(r["template"]["name"] for r in reqs) == ["pagerank", "sssp", "wcc"]
    (sssp,) = (r for r in reqs if r["template"]["name"] == "sssp")
    assert f"src={sssp['start']}," in sssp["text"] and ref.out_degree("KNOWS")[sssp["start"]] >= 1
    assert sssp["rows"] == SSSP.answer(ref, sssp["template"], sssp["start"])["vid"].size
    assert all(r["rows"] == ref.n for r in reqs if r is not sssp)


# ---------------------------------------------------------------------------
# the builder and the rehearsals
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def deployment():
    gen = loader.module("reference/generators", CFG["reference"]["generator"])
    tables = gen.generate(CFG["rehearse"], 2 ** 31 + 4748)
    said = []
    dep = loader.module("builders", CFG["builder"]).build(CFG, CFG["rehearse"], tables, said.append)
    yield dep, RefGraph(tables, False), said
    dep.close()


def test_the_builder_stands_one_chip_up_and_every_new_span_series_and_the_gauge_move(deployment):
    from nebula_tpu.algo import engine
    from nebula_tpu.utils import trace
    from nebula_tpu.utils.stats import stats
    dep, ref, said = deployment
    assert dep.rt.local_mode and not dep.served and set(dep.stages) == {"snapshot_s", "pin_s"}
    assert "both directions of KNOWS and a Person row a vertex" in said[0] and len(said) == 1
    snap = dep.store.snap
    assert int(snap.tags["Person"].present.sum()) == ref.n
    engine._graph_cache.clear(), engine._dev_cache.clear()       # other tests' graphs
    s = dep.open_session()
    start = int(np.flatnonzero(ref.out_degree("KNOWS"))[0])
    c0 = stats().snapshot()
    for name, op in (("pagerank", PR), ("wcc", WCC), ("sssp", SSSP)):
        got = s.execute({"template": TEMPLATES[name], "start": start})
        want = op.answer(ref, TEMPLATES[name], start)
        assert got.error is None and got.n_rows == op.count(ref, TEMPLATES[name], start)
        bad, gap, _ = op.compare(got, want)
        assert bad == 0 and (gap or 0.0) <= CFG["limits"]["float_rel_gap"]
    c1 = stats().snapshot()

    def moved(k):
        return c1.get(k, 0) - c0.get(k, 0)
    # three graphs flattened and sorted, three edge sets and three runs' arrays put, three assemblies
    assert [moved(f"{k}.count") for k in SERIES] == [6, 6, 3]
    assert all(moved(f"{k}.sum") > 0 for k in SERIES)
    rows = {"pagerank": ref.n_edges("KNOWS"), "wcc": 2 * ref.n_edges("KNOWS"),
            "sssp": ref.n_edges("KNOWS")}
    for a, n in rows.items():
        iters = moved(f"algo_iterations{{algo={a}}}")
        assert iters >= 1 and moved(f"algo_edge_visits{{algo={a}}}") == n * iters
        assert moved(f"algo_iter_us{{algo={a}}}.count") == iters
    assert moved("algo_iterations{algo=pagerank}") == 10
    # every iteration is a kernel run, and nothing fell back
    assert moved("tpu_kernel_runs") == sum(moved(f"algo_iterations{{algo={a}}}") for a in rows)
    assert not any(moved(k) for k in c1 if k.startswith(("tpu_host_fallback", "algo_fallback")))
    # ... and the statement's own stats hold what the series were settled from
    st = got.stats
    assert st.steps == moved("algo_iterations{algo=sssp}") and st.result_edges == got.n_rows
    assert st.device_s > 0 and st.put_s > 0 and st.fetch_s > 0 and st.mat_s > 0
    assert st.total_s >= st.device_s + st.put_s + st.fetch_s + st.mat_s and st.retries == 0
    assert st.fetch_bytes == 8 * snap.num_parts * snap.vmax and st.fetch_bytes_kept == 8 * ref.n
    for series in ("tpu_kernel_s", "tpu_put_s", "tpu_fetch_s", "tpu_queue_s", "tpu_mat_s"):
        assert moved(f"{series}.count") == 3
    assert moved("tpu_fetch_bytes") == 3 * st.fetch_bytes
    assert moved("tpu_put_s.sum") == pytest.approx(moved("algo_put_s.sum"))
    assert moved("tpu_mat_s.sum") >= moved("algo_assemble_s.sum")
    held = sum(int(a.nbytes) for _, arrs in engine._dev_cache.values() for a in arrs.values())
    assert c1["tpu_algo_bytes_resident"] == held > 4 * 4 * ref.n_edges("KNOWS")
    # a statement that enters with no trace active is rooted, and its phases fold
    assert [r["name"] for r in trace.trace_store().list(limit=3)] == ["query:tpu.algo"] * 3
    assert moved("stmt_phase_n{phase=other}") == 3
    for ph in ("exec", "put", "dispatch", "materialise", "queue"):
        assert moved(f"stmt_phase_n{{phase={ph}}}") >= 3, ph
    assert moved("stmt_phase_n{phase=dispatch}") == moved("tpu_kernel_runs")
    # a second run prepares and uploads nothing again: only its own arrays and its rows
    s.execute({"template": TEMPLATES["wcc"], "start": start})
    c2 = stats().snapshot()
    assert [c2[f"{k}.count"] - c1[f"{k}.count"] for k in SERIES] == [0, 1, 1]
    assert c2["tpu_algo_bytes_resident"] == held
    assert "no operation 'go'" in s.execute({"template": {"op": "go"}, "start": 0}).error


def test_a_statements_spans_are_named_in_the_phase_vocabulary():
    from nebula_tpu.utils import trace
    for name, phase in (("query:tpu.algo", "other"), ("tpu:algo_iter", "dispatch"),
                        ("algo:prepare", "exec"), ("algo:put", "put"),
                        ("algo:assemble", "materialise")):
        assert trace.phase_of(name) == phase and phase in trace.PHASES
    from benchmarks.lib import trace as T
    assert T.phase_of("algo:assemble") == "materialise"   # a gap's label, with no edit there


@pytest.mark.parametrize("trace,control", [(0, "f32"), (1, "wcc_split")])
def test_a_rehearsal_is_held_to_the_reference_and_refuses_its_control(
        trace, control, capsys, jax_config_restored):  # noqa: F811
    from nebula_tpu.utils.stats import stats
    c0 = stats().snapshot()
    rc = bench_run.main(["--seconds", "1", "--rehearse", "--workload", CELL, "--seed",
                         str(2 ** 31 + 47 + trace), "--trace", str(trace), "--control", control])
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    c1 = stats().snapshot()
    assert rc == 0 and line["rehearsal"]["checks_passed"] is True, out[-3000:]
    assert line["failed"] == 0 and line["attempted"] % 3 == 0 and line["attempted"] >= 3
    checks = {k: v["value"] for k, v in line["checks"].items()}
    assert checks["rows_mismatched"] == 0 and checks["float_rel_gap"] <= 1e-9
    assert checks["tpu_host_fallback_moved"] == 0
    assert checks["device_statements_without_kernel_run"] == 0
    assert line["checks"]["float_rel_gap"]["limit"] == 1e-9
    assert line["control"]["correct"] is False
    if control == "f32":
        assert line["control"]["mismatched"] == 0 and 1e-9 < line["control"]["float_rel_gap"] < 1e-6
    else:
        assert line["control"]["mismatched"] >= 1
    assert "the run's algo series: algo_prepare_s" in out and "tpu_algo_bytes_resident" in out
    runs = c1.get("algo_runs{algo=wcc,mode=device}", 0) - c0.get("algo_runs{algo=wcc,mode=device}", 0)
    assert runs >= 2 and not any(k.startswith("algo_runs") and "mode=host" in k and
                                 c1[k] != c0.get(k, 0) for k in c1)
    if not trace:
        assert set(line["metrics"]) == {"stmt_p50_ms", "stmts_per_s", "setup_s"}
        return
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert {"algo.iter_ms", "algo.iters_per_stmt", "algo.prepare_ms", "algo.assemble_ms",
            "xla.compiles_in_window", "host.cpu_cores_busy"} <= set(got)
    assert got["xla.compiles_in_window"] == 0 and got["algo.prepare_ms"] == 0
    # a share of the chip's peak: nothing to read where there is no chip (no peaks)
    assert "kernel.algo_roofline" not in got
    assert "kernel.algo_roofline" not in line["rehearsal"]["cpu_backend_readings"]
    assert 0 < line["device"]["busy_s"] < line["device"]["window_s"]
    # the metrics without a list: the statement's own TraverseStats and the series it settles
    assert set(UNLISTED) - {"device.idle_share"} <= set(got)
    assert "device.idle_share" in line["rehearsal"]["cpu_backend_readings"]
    assert got["dispatch.retries_per_stmt"] == got["dispatch.refetches_per_stmt"] == 0
    assert 90 < got["dispatch.fetch_kept_share"] <= 100   # the final state, all of it kept
    assert got["dispatch.device_ms"] > 0 and got["dispatch.put_ms"] > 0
    assert got["dispatch.device_ms"] == pytest.approx(
        got["algo.iter_ms"] * got["algo.iters_per_stmt"], rel=0.5)
