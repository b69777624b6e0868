"""The cell `snb-sf1.write-read-8s-compact` (PR 37), piece by piece: the
manifest's entries, the configuration against `snb-sf1-rw`, the
reference operation that owns its sources and fixes a read-back's answer
at its write's acknowledgement, the driver's ownership rule and its
in-flight replies, the four readers, and the cell's own rehearsal, whose
compaction falls inside the window and whose controls are refused."""
from __future__ import annotations

import json
import os
import sys
import threading
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import loader  # noqa: E402
from benchmarks.lib.reply import Columns, Reply  # noqa: E402
from benchmarks.lib.requests import make_requests, op_module  # noqa: E402
from benchmarks.reference.graph import RefGraph  # noqa: E402

MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELL, CONFIG, MIX = "snb-sf1.write-read-8s-compact", "snb-sf1-rw-compact", "iu8-is3-8s-compact"
NEW = ("compact.rebuild_ms", "compact.swap_hold_ms", "compact.failures", "delta.gate_wait_ms")


def limit(seconds):
    """A time limit of the test's own: it fails, late, instead of
    holding the suite (no plugin to kill it is installed)."""
    def wrap(fn):
        import functools

        @functools.wraps(fn)
        def run(*a, **kw):
            t0 = time.monotonic()
            out = fn(*a, **kw)
            took = time.monotonic() - t0
            assert took < seconds, f"{fn.__name__} took {took:.1f}s (limit {seconds}s)"
            return out
        return run
    return wrap


def _run(seed=2 ** 31 + 37, persons=300, degree=6, requests=None):
    mix = loader.data("traffic", MIX)
    if requests:
        mix["requests"] = requests
    tables = loader.module("reference/generators", "snb_tables").generate(
        {"persons": persons, "degree": degree}, seed)
    ref = RefGraph(tables, True)
    return op_module("update_stream"), mix, ref, make_requests(mix, ref, seed)


# -- the manifest and the configuration ---------------------------------------


@limit(5)
def test_the_cell_is_the_issues():
    w = next(x for x in MANIFEST["workloads"] if x["name"] == CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, MIX, 1) and len(w["why"]) <= 200
    c = next(x for x in MANIFEST["configs"] if x["name"] == CONFIG)
    assert c["file"] == f"benchmarks/configs/{CONFIG}.json" and len(c["source"]) <= 200
    mix, one = loader.data("traffic", MIX), loader.data("traffic", "iu8-is3-1s")
    assert (mix["driver"], mix["sessions"], mix["requests"], mix["warmup_rounds"]) == \
        ("closed_loop_rw_owned", 8, 32, 2)
    assert mix["trace_seconds"] == 3 and mix["rehearsal_controls"] == ["stale_read", "f32"]
    (t,), (t1,) = mix["templates"], one["templates"]
    assert t["op"] == "update_stream"
    assert {k: v for k, v in t.items() if k != "op"} == {k: v for k, v in t1.items() if k != "op"}
    cfg, rw = loader.data("configs", CONFIG), loader.data("configs", "snb-sf1-rw")
    assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"] == rw["reduced"]
    for k in ("sizes", "reference", "limits", "reduced_why", "chips"):
        assert cfg[k] == rw[k], k
    assert {k: v for k, v in cfg["fixes"].items() if k != "load"} == \
        {k: v for k, v in rw["fixes"].items() if k != "load"}
    assert cfg["limits"] == {"float_rel_gap": 0, "rows_mismatched": 0}
    assert cfg["builder"] == "local_cluster_rw_backlog" and cfg["probe_op"] == "update_stream"
    assert len(cfg["guarantees"]) == 4 and "during and after a compaction" in cfg["guarantees"][1]
    assert [a == b for a, b in zip(cfg["guarantees"], rw["guarantees"])] == [True, False, True, True]
    assert 0.70 <= cfg["backlog"]["fill"] <= 0.74 and cfg["rehearse"]["backlog_fill"] < 0.75
    assert {"partitions", "backlog_fill", "overwrites"} <= set(cfg["assumed"])
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in NEW:                                  # by name, wherever they stand
        m = by_name[name]
        assert CELL in m["workloads"] and m["layer"] == "delta plane"
        assert (m["source"], m["better"]) == ("program_counter", "lower")
        assert m["moves"] == ("stmt_p50_ms" if name == "delta.gate_wait_ms" else "stmts_per_s")
    import benchmarks.run as bench_run
    assert {m["name"] for m in bench_run.metrics_for(MANIFEST, "end_to_end", CELL)} == \
        {m["name"] for m in MANIFEST["end_to_end"] if "workloads" not in m}


@limit(5)
def test_what_the_cell_adds_imports_nothing_of_the_program_where_it_must_not():
    for rel in ("reference/ops/update_stream.py", "drivers/closed_loop_rw_owned.py"):
        assert "nebula_tpu" not in open(os.path.join(ROOT, "benchmarks", rel)).read(), rel


# -- the reference operation ------------------------------------------------------


@limit(20)
def test_a_read_backs_answer_is_fixed_at_its_writes_acknowledgement():
    op, mix, ref, requests = _run()
    wr = op_module("write_read")
    t = requests[0]["template"]
    a, b = requests[0], requests[1]
    assert op.answer(ref, t, a["start"])["d"].size == a["rows"] == op.rows_now(a)
    w1 = op.next_write(a)
    op.acknowledged(a, w1)
    fixed = op.answer(ref, t, a["start"])
    assert fixed["d"].size == a["rows"] + 1 and fixed["w"].max() == w1["w"]
    # another source's writes, and a backlog, leave it as it was fixed
    for _ in range(3):
        op.acknowledged(b, op.next_write(b))
    writes, text = op.backlog(40)
    assert text.startswith("INSERT EDGE KNOWS(w, f) VALUES ") and text.count("->") == len(writes) == 40
    op.backlog_acknowledged(writes)
    untouched = a["start"] not in {w["src"] for w in writes}
    assert (op.answer(ref, t, a["start"]) is fixed) == untouched
    for w in writes:                                  # new edges, noted like any write
        got = op.answer(ref, t, w["src"])
        assert (got["w"][got["d"] == w["dst"]] == w["w"]).all() and got["w"].max() >= w["w"]
        assert op.rows_now(op.read_of(w["src"])) == got["d"].size
    assert op.compare(Columns(fixed), wr.answer(ref, t, a["start"]))[:2] == (0, 0.0) or not untouched
    # the controls break it as they break write_read's
    stale = loader.module("controls", "stale_read").broken(fixed)
    assert op.compare(stale, fixed)[0] >= 1
    bad, gap, _ = op.compare(loader.module("controls", "f32").broken(fixed), fixed)
    assert bad == 0 and gap > 1e-9


@limit(20)
def test_what_a_source_writes_does_not_depend_on_the_interleaving():
    op, mix, ref, requests = _run()
    order = [requests[i] for i in (0, 1, 2, 0, 1, 2, 0)]
    first = []
    for r in order:
        w = op.next_write(r)
        op.acknowledged(r, w)
        first.append((r["idx"], w["dst"], w["f"]))
    op, mix, ref, requests = _run()                   # a new run, another order
    second = []
    for r in [requests[i] for i in (2, 2, 1, 0, 0, 1, 0)]:
        w = op.next_write(r)
        op.acknowledged(r, w)
        second.append((r["idx"], w["dst"], w["f"]))
    assert sorted(first) == sorted(second)


@limit(20)
def test_two_sessions_writing_one_source_at_once_is_an_error():
    op, mix, ref, requests = _run()
    w = op.next_write(requests[0])
    caught = []

    def other():
        try:
            op.next_write(requests[0])
        except RuntimeError as ex:
            caught.append(str(ex))
    th = threading.Thread(target=other)
    th.start()
    th.join(10)
    assert caught and "two sessions" in caught[0]
    op.acknowledged(requests[0], w)
    th = threading.Thread(target=lambda: op.acknowledged(requests[0], op.next_write(requests[0])))
    th.start()
    th.join(10)                                       # one after the other is fine
    assert op.rows_now(requests[0]) >= requests[0]["rows"] + 1


# -- the driver ------------------------------------------------------------------


class _Stub:
    """A session that acknowledges and answers from the reference, slowly
    enough for the window to close with pairs in flight."""
    seen = None

    def __init__(self, op, sid, pause=0.0):
        self.op, self.sid, self.pause, self.mine = op, sid, pause, []

    def execute(self, request):
        self.mine.append(request["idx"])
        self.op.acknowledged(request, self.op.next_write(request))
        time.sleep(self.pause)
        return Reply(n_rows=self.op.rows_now(request))


@limit(30)
def test_the_driver_gives_every_source_to_one_session_and_checks_what_was_in_flight():
    op, mix, ref, requests = _run()
    driver = loader.module("drivers", "closed_loop_rw_owned")
    sessions = [_Stub(op, s) for s in range(8)]
    recs, last, _, _ = driver.run(sessions, requests, rounds=2)
    assert len(recs) == 64 and sorted(last) == list(range(32)) and all(r.ok for r in recs)
    for s in sessions:                                # idx % 8 == s, in order, round after round
        assert s.mine == [i for i in range(32) if i % 8 == s.sid] * 2
    assert all(r.session == r.idx % 8 for r in recs)
    # a window: closed by the first completion at or after its seconds; the
    # pairs in flight then are outside it, and their replies are the last
    sessions = [_Stub(op, s, pause=0.05) for s in range(8)]
    before = {r["idx"]: op.rows_now(r) for r in requests}
    recs, last, t0, t1 = driver.run(sessions, requests, seconds=0.2)
    inwin, late = [r for r in recs if r.in_window], [r for r in recs if not r.in_window]
    assert inwin and 1 <= len(late) <= 7 and t1 - t0 >= 0.2
    assert all(r.t_done <= t1 for r in inwin) and all(r.t_done >= t1 for r in late)
    for r in late:                                    # acknowledged, in the book, and checked
        assert last[r.idx].n_rows == op.rows_now(requests[r.idx]) >= before[r.idx]
    for idx, reply in last.items():
        assert reply.n_rows == op.rows_now(requests[idx])
    # fewer requests than sessions: the others have nothing to send
    recs, last, _, _ = driver.run([_Stub(op, s) for s in range(8)], requests[:3], rounds=1)
    assert len(recs) == 3 and sorted(last) == [0, 1, 2]
    with pytest.raises(ValueError):
        driver.run(sessions, requests, rounds=1, whole_rounds=True)


@limit(20)
def test_a_reply_with_the_wrong_count_of_the_moment_is_a_failed_operation():
    op, mix, ref, requests = _run()
    driver = loader.module("drivers", "closed_loop_rw_owned")

    class Stale(_Stub):
        def execute(self, request):
            reply = super().execute(request)
            return Reply(n_rows=reply.n_rows - 1) if request["idx"] == 5 else reply
    recs, _, _, _ = driver.run([Stale(op, s) for s in range(8)], requests, rounds=1)
    assert [r.idx for r in recs if not r.ok] == [5]


# -- the readers ---------------------------------------------------------------------


def _ctx(moved, n=10):
    return {"records": [None] * n, "counter": lambda name: moved.get(name, 0)}


@limit(5)
def test_the_four_readers(monkeypatch):
    from nebula_tpu.utils.stats import stats
    read = {m: loader.module("layers", m).read for m in NEW}
    for m in NEW:
        assert loader.module("layers", m).NEEDS
    monkeypatch.setattr(stats(), "snapshot", lambda: {
        "tpu_compact_build_s.count": 1, "tpu_delta_gate_wait_us.sum": 5.0})
    moved = {"tpu_compact_build_s.sum": 0.25, "tpu_compact_build_s.count": 1,
             "tpu_compact_swap_s.sum": 0.04, "tpu_compact_swap_s.count": 1,
             "tpu_compactions": 1, "tpu_compaction_failures": 0,
             "tpu_delta_gate_wait_us.sum": 30_000.0}
    assert {m: read[m](_ctx(moved)) for m in NEW} == {
        "compact.rebuild_ms": 250.0, "compact.swap_hold_ms": 40.0, "compact.failures": 0,
        "delta.gate_wait_ms": 3.0}
    assert read["compact.failures"](_ctx(dict(moved, tpu_compaction_failures=2))) == 2
    # a window that missed its compaction prints neither time, and is seen by that
    quiet = {"tpu_delta_gate_wait_us.sum": 10_000.0}
    assert read["compact.rebuild_ms"](_ctx(quiet)) is None
    assert read["compact.swap_hold_ms"](_ctx(quiet)) is None
    assert read["compact.failures"](_ctx(quiet)) == 0 and read["delta.gate_wait_ms"](_ctx(quiet)) == 1.0
    # a build that ended in the window whose swap did not: no rebuild time either
    assert read["compact.rebuild_ms"](_ctx({"tpu_compact_build_s.count": 1,
                                             "tpu_compact_build_s.sum": 1.0})) is None
    # the parent keeps none of it
    monkeypatch.setattr(stats(), "snapshot", lambda: {"tpu_pins": 3})
    assert {m: read[m](_ctx({"tpu_pins": 1})) for m in NEW} == dict.fromkeys(NEW)


# -- the cell's rehearsal ---------------------------------------------------------------


def _fill_that_crosses_in_the_window(seed):
    """The rehearsal's backlog comes from ONE source, so the fullest
    buffer is the out-buffer of that source's part, and every pair from
    a request source of that part adds one row to it.  -> the backlog
    fill that leaves that buffer ONE row under the watermark after the
    warm-up (the first statement and two rounds), worked out from the
    writes the reference will draw (they are the seed's) and the
    program's own partitioning."""
    from nebula_tpu.graphstore.store import stable_vid_hash
    cfg, mix = loader.data("configs", CONFIG), loader.data("traffic", MIX)
    sizes = cfg["rehearse"]
    mix["requests"] = sizes["requests"]
    P = cfg["fixes"]["space"]["partition_num"]
    tables = loader.module("reference/generators", "snb_tables").generate(sizes, seed)
    ref = RefGraph(tables, True)
    op = op_module("update_stream")
    requests = make_requests(mix, ref, seed)
    (w,), _ = op.backlog(1, sizes["backlog_sources"])
    part = stable_vid_hash(w["src"]) % P
    mine = [r for r in requests if stable_vid_hash(r["start"]) % P == part]
    assert mine and w["src"] not in {r["start"] for r in requests}, \
        "this seed's backlog source shares its part with no request: take another seed"
    rows = set()
    for r in [requests[-1]] + requests * int(mix["warmup_rounds"]):
        wr = op.next_write(r)
        op.acknowledged(r, wr)
        if r in mine:
            rows.add((r["start"], wr["dst"]))
    return (768 - 1 - len(rows)) / 1024


def _rehearsal(capsys, monkeypatch, trace, control, seed=7):
    import benchmarks.run as bench_run
    from nebula_tpu.utils.stats import stats
    fill = _fill_that_crosses_in_the_window(seed)
    data = loader.data

    def tuned(kind, name):
        d = data(kind, name)
        if (kind, name) == ("configs", CONFIG):
            d["rehearse"]["backlog_fill"] = fill
        return d
    monkeypatch.setattr(loader, "data", tuned)
    c0 = stats().snapshot()
    rc = bench_run.main(["--workload", CELL, "--seed", str(seed), "--seconds", "2", "--trace",
                         str(trace), "--rehearse", "--control", control])
    c1 = stats().snapshot()
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    return rc, line, out, {k: v - c0.get(k, 0) for k, v in c1.items()
                           if isinstance(v, (int, float)) and v != c0.get(k, 0)}


@pytest.fixture()
def jax_config_restored():
    import jax
    keys = ("jax_compilation_cache_dir", "jax_persistent_cache_min_entry_size_bytes",
            "jax_persistent_cache_min_compile_time_secs")
    before = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in before.items():
        jax.config.update(k, v)


@limit(90)
@pytest.mark.parametrize("trace,control", [(0, "stale_read"), (1, "f32")])
def test_the_cells_rehearsal_compacts_inside_its_window(trace, control, capsys, monkeypatch,
                                                        jax_config_restored):
    rc, line, out, moved = _rehearsal(capsys, monkeypatch, trace, control)
    assert rc == 0 and line["correct"] is False
    assert line["rehearsal"]["checks_passed"] is True, out[-3000:]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["checks"]["tpu_host_fallback_moved"]["value"] == 0
    assert line["control"]["correct"] is False, "the check let the control through"
    said = [ln for ln in out.splitlines() if "backlog:" in ln]
    assert said and "tpu_pins +0" in said[0] and "tpu_compactions +0" in said[0]
    # the one compaction of the run began and ended inside the window's run
    window = out[out.index("set-up stages:"):]
    assert moved.get("tpu_compactions") == 1 == moved.get("tpu_compact_swap_s.count")
    assert not moved.get("tpu_compaction_failures")
    assert moved.get("tpu_pins") == 1, "the deployment's first pin and no other"
    assert "0 backend compiles inside" in window
    if trace:
        got = line["metrics"]
        assert set(NEW) <= set(got), sorted(got)
        assert got["compact.failures"]["value"] == 0
        assert got["compact.rebuild_ms"]["value"] > 0 and got["compact.swap_hold_ms"]["value"] > 0
        assert got["delta.gate_wait_ms"]["value"] >= 0
        assert got["xla.compiles_in_window"]["value"] == 0
        assert {"dispatch.queue_ms", "host.cpu_cores_busy"} <= set(got)
    else:
        assert set(line["metrics"]) == {"stmt_p50_ms", "stmts_per_s", "setup_s"}
        assert all(m["value"] > 0 for m in line["metrics"].values())
