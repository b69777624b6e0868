"""Tier-1 tests of what `snb-sf300-paths-proxy.bfs5-4chip` brings to the
benchmark (PR 43), on four of tier-1's eight virtual devices: the sharded
BFS, the one-chip BFS and the plain reference level for level on seeded
`knows_symmetric` graphs; the `bfs_levels_wide` reference operation held
to `bfs_levels`; the configuration's full size by shapes alone; the
`prebuilt_mesh_paths` builder; both controls refused; the exchange's byte
count and the three new readers on hand-built `ctx`s, with their manifest
entries found by name; an untraced and a traced rehearsal of the cell.
The cell's plain rehearsals, control look-ups and pieces test are
test_benchmark.py's parametrised cases."""
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
from benchmarks.lib import arith, loader, trace as T  # noqa: E402
from benchmarks.lib.bfs_bytes import bfs_bytes  # noqa: E402
from benchmarks.lib.bfs_mesh_bytes import bfs_mesh_bytes  # noqa: E402
from benchmarks.reference.graph import RefGraph  # noqa: E402

from test_phase_metrics import jax_config_restored  # noqa: E402,F401

CELL, CONFIG, MIX = "snb-sf300-paths-proxy.bfs5-4chip", "snb-sf300-paths-proxy", "bfs5-mesh-1s"
MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CFG = loader.data("configs", CONFIG)
SCHEMA = CFG["fixes"]["schema"]["edges"]
TEMPLATE = loader.data("traffic", MIX)["templates"][0]
GEN = loader.module("reference/generators", "knows_symmetric")
MESH = loader.module("builders", "prebuilt_mesh")
BUILDER = loader.module("builders", "prebuilt_mesh_paths")
STORE = loader.module("builders", "prebuilt_snapshot").SnapshotStore
WIDE = loader.module("reference/ops", "bfs_levels_wide")
PLAIN = loader.module("reference/ops", "bfs_levels")
SEEDS = [2 ** 31 + 4301, 4302, 4303]
NEW = {"kernel.bfs_roofline.mesh": ("%", "higher", "kernels", "stmts_per_s"),
       "mesh.bfs_exchange_ms": ("ms", "lower", "mesh exchange", "stmt_p50_ms"),
       "mesh.bfs_busy_skew": ("ratio", "lower", "mesh exchange", "stmts_per_s")}
COUNTERS = ("tpu_bfs_runs", "tpu_bfs_levels", "tpu_bfs_levels_bottom_up", "tpu_bfs_edges",
            "tpu_bfs_budget_slots", "tpu_bfs_chunks_run", "tpu_bfs_chunks_budget",
            "tpu_bfs_exchange_bytes", "tpu_bfs_widest_level_slots.sum",
            "tpu_bfs_widest_level_slots.count", "tpu_kernel_runs")


def tables_without(seed, victim):
    """The rehearsal graph of `seed` with every friendship of `victim`
    taken out of both halves: a person no start reaches."""
    t = GEN.generate(CFG["rehearse"], seed)
    e = t["edges"]["KNOWS"]
    keep = (e["src"] != victim) & (e["dst"] != victim)
    t["edges"]["KNOWS"] = {k: v[keep] for k, v in e.items()}
    return t


# ---------------------------------------------------------------------------
# sharded against one chip against the reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", SEEDS)
def test_sharded_one_chip_and_reference_give_every_vertex_the_same_level(seed):
    from nebula_tpu.tpu.runtime import TpuRuntime
    victim = 17
    tables = tables_without(seed, victim)
    ref = RefGraph(tables, CFG["reference"]["dedupe_last"])
    P = int(CFG["rehearse"]["parts"])
    snap = MESH.snapshot_from_pairs(tables, SCHEMA, P, MESH.SPACE)
    deg = ref.out_degree("KNOWS")
    assert deg[victim] == 0
    starts = [int(np.flatnonzero(deg == 1)[0]), int(np.argmax(deg)), int(np.flatnonzero(deg > 1)[seed % 97])]
    rts = [TpuRuntime(n_devices=P), TpuRuntime(n_devices=1)]
    assert not rts[0].local_mode and rts[0].mesh_size == P and rts[1].local_mode
    try:
        sessions = []
        for rt in rts:
            rt.pin_prebuilt(snap)
            sessions.append(BUILDER.Session(rt, STORE(snap)))
        for v in starts:
            want = WIDE.answer(ref, TEMPLATE, v)
            assert want["level"][victim] == -1 and want["level"][v] == 0
            got = [s.execute({"template": TEMPLATE, "start": v}) for s in sessions]
            for reply in got:
                assert reply.error is None and reply.n_rows == WIDE.count(ref, TEMPLATE, v)
                assert reply.column("level").shape == (ref.n,)
                assert WIDE.compare(reply, want)[0] == 0
            mesh_st, local_st = got[0].stats, got[1].stats
            # the slots a level expands are the reference's out-edges of its frontier,
            # on the mesh as they are top-down on one chip
            assert mesh_st.hop_edges == [e for _, e in WIDE.profile(TEMPLATE, v)[0]]
            assert not any(mesh_st.bottom_up) and mesh_st.shards == P
            assert mesh_st.exchange_bytes == bfs_mesh_bytes(5, P, snap.vmax) > 0
            assert (local_st.shards, local_st.exchange_bytes) == (1, 0)
    finally:
        for rt in rts:
            rt.unpin(MESH.SPACE)


# ---------------------------------------------------------------------------
# the reference operation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", SEEDS)
def test_the_wide_operation_is_bfs_levels_level_for_level_and_profile_for_profile(seed, monkeypatch):
    ref = RefGraph(tables_without(seed, 3), False)
    deg = ref.out_degree("KNOWS")
    took = []
    real = WIDE._mark_wide
    monkeypatch.setattr(WIDE, "_mark_wide", lambda *a: (took.append(1), real(*a))[1])
    for v in (int(np.argmax(deg)), int(np.flatnonzero(deg == 1)[-1]), 3, seed % ref.n):
        for t in (TEMPLATE, dict(TEMPLATE, max_steps=2)):
            level, expanded = WIDE.levels(ref, t["over"], v, t["max_steps"])
            want_level, want_expanded = PLAIN.levels(ref, t["over"], v, t["max_steps"])
            assert level.dtype == want_level.dtype and np.array_equal(level, want_level)
            assert expanded == want_expanded and len(expanded) == t["max_steps"]
        assert WIDE.count(ref, TEMPLATE, v) == PLAIN.count(ref, TEMPLATE, v)
        assert WIDE.profile(TEMPLATE, v) == PLAIN.profile(TEMPLATE, v)
        assert WIDE.params(ref, TEMPLATE, v) == PLAIN.params(ref, TEMPLATE, v)
    # both ways of a level were taken: the one pass on the dense levels, `_slots` on the rest
    assert took and len(took) < 4 * 7
    assert WIDE.count(ref, TEMPLATE, 3) == 1              # nobody's friend reaches nobody
    assert WIDE.compare is not None and WIDE.profile(TEMPLATE, 5) is None
    other = RefGraph(tables_without(seed, 3), False)      # a second graph empties the memo
    assert WIDE.count(other, TEMPLATE, 9) >= 1 and WIDE.profile(TEMPLATE, 3) is None


def test_the_one_pass_marks_what_slots_marks_on_every_range(monkeypatch):
    ref = RefGraph(GEN.generate(CFG["rehearse"], 77), False)
    csr = ref.csr["KNOWS"]
    rng = np.random.default_rng(5)
    from concurrent.futures import ThreadPoolExecutor
    from benchmarks.reference.graph import _slots
    for ranges in (1, 3, 64, 5000):                       # more ranges than vertices too
        monkeypatch.setattr(WIDE, "RANGES", ranges)
        member = rng.random(ref.n) < 0.3
        seen = np.zeros(ref.n, bool)
        with ThreadPoolExecutor(max_workers=3) as pool:
            WIDE._mark_wide(csr, member, seen, pool)
        want = np.zeros(ref.n, bool)
        want[csr.nbr[_slots(csr, np.flatnonzero(member))[0]]] = True
        assert np.array_equal(seen, want)


@pytest.mark.parametrize("control", ["level_off_by_one", "level_unreached"])
def test_both_controls_are_refused(control):
    ref = RefGraph(GEN.generate(CFG["rehearse"], SEEDS[0]), False)
    want = WIDE.answer(ref, TEMPLATE, 11)
    broken = loader.module("controls", control).broken(want)
    assert broken is not None
    bad, gap, _ = WIDE.compare(broken, want)
    assert bad == 1 and gap is None
    assert control in loader.data("traffic", MIX)["rehearsal_controls"]


# ---------------------------------------------------------------------------
# the manifest, the configuration and its size
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_manifest_entry(name):
    m = next(m for m in MANIFEST["per_layer"] if m["name"] == name)     # wherever it stands
    assert (m["unit"], m["better"], m["layer"], m["moves"]) == NEW[name]
    assert m["source"] == "device_trace" and CELL in m["workloads"]
    layers = {x["layer"] for x in MANIFEST["per_layer"] if x["name"] not in NEW}
    assert m["layer"] in layers                           # a layer the manifest already names
    assert os.path.isfile(loader.path_of("layers", name, ".py"))


def test_the_cell_and_its_configuration():
    w = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, MIX, 4)
    four = [x for x in MANIFEST["workloads"] if x["chips"] == 4]
    assert 2 * len(four) <= len(MANIFEST["workloads"])    # at most half the cells take four chips
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    assert entry["source"] == CFG["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == CFG["reduced"] == list(CFG["reduced_why"])
    assert {"path_reconstruction", "chips"} <= set(entry["reduced"])
    assert "config 5" in entry["source"] and "IC13" in entry["source"]
    mesh_cfg = loader.data("configs", "snb-sf300-proxy")
    paths_cfg = loader.data("configs", "snb-sf100-paths-proxy")
    # the mesh proxy's graph to the letter, so that what is pinned is what it pins
    assert CFG["sizes"] == mesh_cfg["sizes"] and CFG["fixes"]["schema"] == mesh_cfg["fixes"]["schema"]
    assert CFG["rehearse"] == {"persons": 4000, "degree": 8, "max_degree": 64, "parts": 4}
    assert CFG["reference"] == paths_cfg["reference"] and CFG["limits"] == paths_cfg["limits"]
    assert CFG["guarantees"][:2] == paths_cfg["guarantees"][:2] and len(CFG["guarantees"]) == 3
    assert CFG["builder"] == "prebuilt_mesh_paths" and CFG["chips"] == 4
    assert "TpuRuntime.bfs" in CFG["fixes"]["entry"] and "v5e-16" in CFG["fixes"]["deployment"]
    assert set(CFG["assumed"]) == {"statement", "sf300_counts", "parts", "max_degree", "sessions"}
    reported = {m["name"] for m in bench_run.metrics_for(MANIFEST, "end_to_end", CELL)}
    assert reported == {"stmt_p50_ms", "stmts_per_s", "setup_s"}
    mix = loader.data("traffic", MIX)
    single = loader.data("traffic", "bfs5-single")
    assert (mix["driver"], mix["sessions"], mix["requests"], mix["whole_rounds"]) == \
        ("closed_loop", 1, 6, True)
    assert (mix["warmup_rounds"], mix["trace_statements"], mix["trace_seconds"]) == (2, 6, 25)
    # bfs5-single's template letter for letter, but for the reference operation's name
    assert dict(TEMPLATE, op="bfs_levels") == single["templates"][0] and len(mix["templates"]) == 1
    # the ten metrics without a list are this cell's too, as they are bfs5's
    free = {m["name"] for m in MANIFEST["per_layer"] if "workloads" not in m}
    got = {m["name"] for m in bench_run.metrics_for(MANIFEST, "per_layer", CELL)}
    assert got == free | set(NEW)


def test_full_size_is_the_mesh_proxys_bytes_over_one_chip_and_under_it_on_four():
    """By shapes alone, no array of the full size is made; and the part's
    width is over the ladder's old cap, which is what the deployment
    forced."""
    from nebula_tpu.tpu.runtime import TpuRuntime, TpuUnavailable
    from nebula_tpu.utils.memtracker import get_config

    sizes = CFG["sizes"]
    limit = int(get_config().get("tpu_hbm_limit_bytes"))
    rows = sizes["persons"] * sizes["degree"]
    width = MESH.padded_width(rows // sizes["parts"])
    assert width == 50_331_648 and f"{width:,}" in CFG["fixes"]["pinned_bytes"]
    assert {MESH.padded_width(int(f * rows / sizes["parts"])) for f in (0.97, 1.0, 1.03)} == {width}
    need = MESH.snapshot_bytes(sizes["persons"], sizes["parts"], width, SCHEMA["KNOWS"])
    assert need == 12_932_901_936 and f"{need:,}" in CFG["fixes"]["pinned_bytes"]
    assert f"{need // sizes['parts']:,}" in CFG["fixes"]["pinned_bytes"]
    assert need > 1.05 * limit and -(-need // sizes["parts"]) < limit / 3
    shaped = types.SimpleNamespace(num_parts=sizes["parts"], hbm_bytes=lambda: need, space="snb")
    with pytest.raises(TpuUnavailable, match="1 shard"):
        TpuRuntime(n_devices=1)._check_hbm_budget(shaped, "snb")
    TpuRuntime(n_devices=sizes["parts"])._check_hbm_budget(shaped, "snb")     # accepted
    # a level may need nearly a part's rows, and a part's rows pass the traverse ladder's cap
    assert rows // sizes["parts"] > TpuRuntime(n_devices=1).max_cap
    # whole trips of the level loop tile the width: a level capped there still runs by need
    from nebula_tpu.algo.frontier import LEVEL_CHUNK
    assert width % LEVEL_CHUNK == 0
    # a flat index over a part's slots, or over every owner's vertices, fits 32 bits
    assert width < 2 ** 31 and sizes["persons"] < 2 ** 31


# ---------------------------------------------------------------------------
# the exchange's bytes and the readers, on a hand-built ctx
# ---------------------------------------------------------------------------


def test_exchange_bytes_from_shapes_are_what_the_program_says_it_moves():
    from nebula_tpu.tpu.bfs import bfs_exchange_bytes
    assert bfs_mesh_bytes(5, 4, 1_500_000) == 5 * 4 * 4 * 46_875 * 4 == 15_000_000
    assert bfs_mesh_bytes(1, 2, 33) == 2 * 2 * 2 * 4 and bfs_mesh_bytes(0, 4, 100) == 0
    for levels, parts, vmax in ((5, 4, 1_500_000), (3, 2, 1000), (5, 8, 125_000)):
        assert bfs_mesh_bytes(levels, parts, vmax) == bfs_exchange_bytes(parts, vmax, levels)


def _ns(ms):
    return int(ms * 1e6)


A2A = ("%all_to_all.3 = u32[4,1,46875]{2,1,0:T(1,128)S(1)} all-to-all(%pack.3), "
       "channel_id=3, replica_groups={{0,1,2,3}}, dimensions={0}")
GATHER = "%ag.1 = s32[4]{0} all-gather(%total.1), channel_id=9, dimensions={0}"
USER = "%fusion.9 = pred[1500000]{0} fusion(%all_to_all.3, %p.1), kind=kLoop, calls=%fc.9"
LEVEL = "%while.5 = (s32[], pred[6000000]{0}) while(%tuple.5), condition=%c.5, body=%b.5"


def hand_graph():
    pairs = [(0, 1), (0, 2), (1, 3), (2, 3), (3, 4), (4, 5), (5, 6), (7, 8)]
    src = np.array([a for a, b in pairs] + [b for a, b in pairs])
    dst = np.array([b for a, b in pairs] + [a for a, b in pairs])
    z = np.zeros(src.size)
    return RefGraph({"n": 9, "edges": {"KNOWS": {"src": src, "dst": dst, "w": z, "f": z}}})


def _ctx(**over):
    events = {"devices": {
        "/device:TPU:0": [(LEVEL, _ns(10), _ns(20)), (A2A, _ns(20), _ns(24)), (USER, _ns(24), _ns(30))],
        "/device:TPU:1": [(LEVEL, _ns(10), _ns(14)), (A2A, _ns(14), _ns(24)), (USER, _ns(24), _ns(30)),
                          (GATHER, _ns(95), _ns(105))]},
        "spans": [], "marks": {T.SLICE_BEGIN: [(0, 0)], T.SLICE_END: [(_ns(100), _ns(100))],
                               T.STMT: [(_ns(5), _ns(50))]}}
    rec = [types.SimpleNamespace(idx=0, stats=None), types.SimpleNamespace(idx=1, stats=None)]
    ctx = {"events": events, "trace": T.reduce(events, sessions=1), "traced": rec, "chips": 4,
           "peaks": arith.peaks_for("TPU v5 lite"),
           "requests": [{"template": TEMPLATE, "start": 0}, {"template": TEMPLATE, "start": 7}]}
    ctx.update(over)
    return ctx


def test_exchange_reader_sums_the_collectives_of_each_plane_by_opcode():
    mod = loader.module("layers", "mesh.bfs_exchange_ms")
    assert mod.is_collective(A2A) and mod.is_collective(GATHER)
    assert mod.is_collective("all-to-all-done.1") and mod.is_collective("%all-gather-start.2")
    assert mod.is_collective("%x = (u32[4]{0}, u32[4]{0}) all-reduce-start(%a, %b), channel_id=2")
    assert not mod.is_collective(USER) and not mod.is_collective(LEVEL)
    # chip 0: 4 ms; chip 1: 10 ms + the 5 ms of its all-gather inside the slice; two statements
    assert mod.read(_ctx()) == pytest.approx((4 + 15) / 2 / 2)
    quiet = _ctx()
    quiet["events"] = dict(quiet["events"], devices={"/device:TPU:0": [(LEVEL, _ns(10), _ns(20))]})
    assert mod.read(quiet) is None                        # a program with no collective
    assert mod.read(_ctx(events=None)) is None and mod.read(_ctx(traced=[])) is None


def test_skew_reader_is_the_busiest_plane_over_the_mean():
    read = loader.module("layers", "mesh.bfs_busy_skew").read
    # chip 0 busy [10,30) = 20 ms; chip 1 [10,30) + [95,100) = 25 ms
    assert read(_ctx()) == pytest.approx(25 / 22.5)
    assert read(_ctx()) == loader.module("layers", "mesh.busy_skew").read(_ctx())
    assert read(_ctx(events=None)) is None


def test_mesh_roofline_reader_adds_the_exchange_and_divides_by_all_the_chips():
    read = loader.module("layers", "kernel.bfs_roofline.mesh").read
    g = hand_graph()
    WIDE.answer(g, TEMPLATE, 0)
    WIDE.answer(g, TEMPLATE, 7)
    ctx = _ctx()
    busy_s = ctx["trace"]["busy_s"]
    assert busy_s == pytest.approx(0.0225)                # the mean over the planes
    # bfs_bytes as kernel.bfs_roofline counts them (136 from 0, 60 from 7, tests of PR 41) and,
    # a statement, five levels of 4 x 4 rows of ceil(ceil(9 / 4) / 32) = 1 word
    moved = 136 + 60 + 2 * 5 * 4 * 4 * 1 * 4
    assert bfs_bytes(*WIDE.profile(TEMPLATE, 0)) == 136 and bfs_mesh_bytes(5, 4, 3) == 320
    assert read(ctx) == pytest.approx(100.0 * moved / (busy_s * 4 * 819e9))
    assert read(_ctx(trace=None)) is None and read(_ctx(peaks=None)) is None
    assert read(_ctx(traced=[])) is None
    # a request the reference never ran from, or an operation with no profile: nothing to read
    assert read(_ctx(requests=[{"template": TEMPLATE, "start": 4}] * 2)) is None
    go = loader.data("traffic", "go3-single")["templates"][0]
    assert read(_ctx(requests=[{"template": go, "start": 0}] * 2)) is None


# ---------------------------------------------------------------------------
# the builder and the rehearsals
# ---------------------------------------------------------------------------


def test_the_builder_stands_up_a_mesh_of_exactly_four_among_eight_and_answers_in_vid_order():
    import jax
    assert len(jax.devices()) == 8
    tables = GEN.generate(CFG["rehearse"], 2 ** 31 + 4343)
    ref = RefGraph(tables, CFG["reference"]["dedupe_last"])
    said = []
    dep = BUILDER.build(CFG, CFG["rehearse"], tables, said.append)
    try:
        assert dep.rt.mesh_size == 4 and not dep.rt.local_mode and not dep.served
        assert set(dep.stages) == {"snapshot_s", "pin_s"}
        assert "per chip" in said[0] and said[1].startswith("rows a part") and len(said) == 2
        per_chip = dep.rt.snapshots[MESH.SPACE].shard_hbm_bytes()
        assert len(per_chip) == 4 and len(set(per_chip.values())) == 1
        s = dep.open_session()
        start = int(np.argmax(ref.out_degree("KNOWS")))
        reply = s.execute({"template": TEMPLATE, "start": start})
        want = WIDE.answer(ref, TEMPLATE, start)
        assert reply.error is None and reply.n_rows == WIDE.count(ref, TEMPLATE, start)
        assert WIDE.compare(reply, want)[0] == 0          # position i is vid i
        assert reply.stats.shards == 4 and not any(reply.stats.bottom_up)
        # either name of the reference operation, and no other operation
        assert s.execute({"template": dict(TEMPLATE, op="bfs_levels"), "start": start}).n_rows == \
            reply.n_rows
        assert "no operation 'go'" in s.execute({"template": {"op": "go"}, "start": 0}).error
        # a statement the runtime cannot answer ends the run: it is not a failed operation
        from nebula_tpu.tpu.runtime import TpuUnavailable

        def refuse(*a, **kw):
            raise TpuUnavailable("bucket escalation did not converge")
        dep.rt.bfs = refuse
        with pytest.raises(TpuUnavailable, match="did not converge"):
            s.execute({"template": TEMPLATE, "start": start})
    finally:
        dep.close()
    assert "converged BFS launches" in said[2] and said[3].startswith("peak bytes a chip")


@pytest.mark.parametrize("trace,control", [(0, "level_off_by_one"), (1, "level_unreached")])
def test_a_rehearsal_runs_the_sharded_program_and_prints_the_metrics(
        trace, control, capsys, jax_config_restored):  # noqa: F811
    from nebula_tpu.utils.stats import stats
    c0 = stats().snapshot()
    rc = bench_run.main(["--seconds", "1", "--rehearse", "--workload", CELL, "--seed",
                         str(2 ** 31 + 43 + trace), "--trace", str(trace), "--control", control])
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    c1 = stats().snapshot()
    moved = {k: c1.get(k, 0) - c0.get(k, 0) for k in COUNTERS}
    assert rc == 0 and line["rehearsal"]["checks_passed"] is True, out[-3000:]
    assert line["failed"] == 0 and line["checks"]["rows_mismatched"]["value"] == 0
    assert line["control"]["correct"] is False and line["control"]["mismatched"] >= 1
    assert "on a mesh of 4" in out and "peak bytes a chip" in out
    # every statement one converged BFS launch of five top-down levels over four shards
    runs = moved["tpu_bfs_runs"]
    assert runs == moved["tpu_kernel_runs"] == moved["tpu_bfs_widest_level_slots.count"] > 0
    assert moved["tpu_bfs_levels"] == 5 * runs and moved["tpu_bfs_levels_bottom_up"] == 0
    vmax = CFG["rehearse"]["persons"] // 4
    assert moved["tpu_bfs_exchange_bytes"] == runs * bfs_mesh_bytes(5, 4, vmax)
    assert 0 < moved["tpu_bfs_edges"] <= moved["tpu_bfs_budget_slots"]
    # the widest level of the fullest part: under the slots of all four parts, over their mean
    widest = moved["tpu_bfs_widest_level_slots.sum"] / runs
    assert 0 < widest <= moved["tpu_bfs_edges"] / runs
    if not trace:
        assert set(line["metrics"]) == {"stmt_p50_ms", "stmts_per_s", "setup_s"}
        return
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert got["xla.compiles_in_window"] == 0
    # read off the device trace: kept apart on the CPU backend, and the share of the chips'
    # peak is nothing where there is no chip (no peaks)
    off = line["rehearsal"]["cpu_backend_readings"]
    assert not set(NEW) & set(got) and "kernel.bfs_roofline.mesh" not in off
    assert off["mesh.bfs_exchange_ms"]["value"] > 0 and off["mesh.bfs_busy_skew"]["value"] >= 1.0
    assert 0 < line["device"]["busy_s"] < line["device"]["window_s"]
    # the metrics without a list find under `query:tpu.bfs` what they find in bfs5
    assert {"dispatch.device_ms", "dispatch.queue_ms", "dispatch.put_ms", "dispatch.fetch_ms",
            "dispatch.retries_per_stmt", "dispatch.refetches_per_stmt", "host.cpu_cores_busy",
            "dispatch.fetch_kept_share"} <= set(got)
