"""The cell `snb-sf1.write-read` (PR 32), piece by piece: the reference
operation whose answer grows with the acknowledged writes, the control
that is a stale read, the driver that holds a reply to the row count of
the moment, the six readers, and the builder — whose pair is compared
with the reference at the system's small size on the CPU, every
read-back, and whose probe refuses a program that re-pins."""
from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import loader  # noqa: E402
from benchmarks.lib.reply import Columns  # noqa: E402
from benchmarks.lib.requests import make_requests, op_module  # noqa: E402
from benchmarks.reference.graph import RefGraph  # noqa: E402

MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELL = "snb-sf1.write-read"
NEW = ("delta.apply_ms", "delta.put_ms", "delta.keys_per_apply", "delta.repins_per_req",
       "delta.fill_share", "write.ack_ms")
SEED = 2 ** 31 + 32


def _run(seed=SEED, persons=300, degree=6):
    """(op, mix, ref, requests) of a fresh run of the mix."""
    mix = loader.data("traffic", "iu8-is3-1s")
    tables = loader.module("reference/generators", "snb_tables").generate(
        {"persons": persons, "degree": degree}, seed)
    ref = RefGraph(tables, True)
    return op_module("write_read"), mix, ref, make_requests(mix, ref, seed), tables


def test_the_cell_is_the_issues():
    w = next(x for x in MANIFEST["workloads"] if x["name"] == CELL)
    assert (w["config"], w["traffic"], w["chips"]) == ("snb-sf1-rw", "iu8-is3-1s", 1)
    mix = loader.data("traffic", w["traffic"])
    assert (mix["driver"], mix["sessions"], mix["requests"], mix["warmup_rounds"]) == \
        ("closed_loop_rw", 1, 32, 2)
    assert mix["whole_rounds"] is False and mix["trace_seconds"] == 3
    assert sorted(mix["rehearsal_controls"]) == ["f32", "stale_read"]
    (t,) = mix["templates"]
    assert t["op"] == "write_read" and t["new_of"] == 4       # 3 new edges to 1 overwrite
    assert t["write"].startswith("INSERT EDGE KNOWS(w, f) VALUES $v->$u:")
    assert t["text"].startswith("GO 1 STEPS FROM $v OVER KNOWS YIELD dst(edge) AS d, KNOWS.w")
    served, rw = loader.data("configs", "snb-sf1-served"), loader.data("configs", "snb-sf1-rw")
    for k in ("sizes", "rehearse", "reference", "limits", "chips"):
        assert rw[k] == served[k], k
    assert rw["fixes"]["space"] == served["fixes"]["space"]
    assert rw["fixes"]["schema"] == served["fixes"]["schema"]
    assert rw["reduced"] == served["reduced"] + ["update_mix"]
    assert rw["builder"] == "local_cluster_rw" and len(rw["guarantees"]) >= 3
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    assert set(NEW) <= set(by_name)                       # wherever: later PRs append theirs
    for name in NEW:                                      # at least the cell they came with
        assert CELL in by_name[name]["workloads"] and by_name[name]["source"] == "program_counter"


def test_the_reference_imports_nothing_of_the_program():
    for rel in ("reference/ops/write_read.py", "controls/stale_read.py"):
        assert "nebula_tpu" not in open(os.path.join(ROOT, "benchmarks", rel)).read()


def test_the_answer_grows_with_the_acknowledged_writes_and_the_last_write_wins():
    op, mix, ref, requests, tables = _run()
    assert len({r["start"] for r in requests}) == len(requests) == 32   # one source a request
    t = requests[0]["template"]
    gen_w = int(tables["edges"]["KNOWS"]["w"].max())
    seen_u = set()
    for replay in range(3):
        for req in requests:
            v = req["start"]
            before = op.answer(ref, t, v)
            n0 = op.rows_now(req)
            assert n0 == before["d"].size == op.count(ref, t, v)
            wr = op.next_write(req)
            assert op.rows_now(req) == n0                    # nothing noted before the ack
            assert wr["w"] > gen_w and float(np.float32(wr["f"])) != wr["f"]
            assert wr["text"] == (f"INSERT EDGE KNOWS(w, f) VALUES {v}->{wr['dst']}:"
                                  f"({wr['w']}, {wr['f']!r})")
            overwrite = req["idx"] % 4 == 3
            assert (wr["dst"] in before["d"]) == overwrite
            op.acknowledged(req, wr)
            after = op.answer(ref, t, v)
            assert op.rows_now(req) == after["d"].size == n0 + (0 if overwrite else 1)
            at = int(np.flatnonzero(after["d"] == wr["dst"])[0])
            assert (after["w"][at], after["f"][at]) == (wr["w"], wr["f"])
            assert after["w"].max() == wr["w"]               # the newest is the largest w
            assert np.unique(after["d"]).size == after["d"].size
            if not overwrite:
                assert (v, wr["dst"]) not in seen_u          # a fresh one at every replay
                seen_u.add((v, wr["dst"]))
    # 3 of every 4 requests added an edge; the generator's other rows stand as they were
    assert sum(op.rows_now(r) - r["rows"] for r in requests) == 3 * 24
    req = requests[0]
    base = ref.go([req["start"]], 1, ["KNOWS"], None, ("d", "w", "f"))[0]
    now = op.answer(ref, t, req["start"])
    untouched = ~np.isin(base["d"], [d for d in now["d"] if now["w"][now["d"] == d][0] > gen_w])
    assert set(zip(base["d"][untouched], base["w"][untouched])) <= set(zip(now["d"], now["w"]))


def test_a_new_run_starts_a_new_book():
    op, mix, ref, requests, _ = _run()
    wr = op.next_write(requests[1])
    op.acknowledged(requests[1], wr)
    assert op.rows_now(requests[1]) == requests[1]["rows"] + 1
    op2, _, ref2, requests2, _ = _run()
    assert op2 is op and ref2 is not ref
    assert [r["text"] for r in requests2] == [r["text"] for r in requests]
    assert op.rows_now(requests2[1]) == requests2[1]["rows"]
    assert op.next_write(requests2[1]) == wr                 # and draws the same writes


def test_stale_read_is_refused_after_an_insert_and_after_an_overwrite():
    op, mix, ref, requests, _ = _run()
    stale = loader.module("controls", "stale_read").broken
    t = requests[0]["template"]
    for req in (requests[0], requests[3]):                   # a new edge, an overwrite
        v = req["start"]
        before = op.answer(ref, t, v)
        wr = op.next_write(req)
        op.acknowledged(req, wr)
        want = op.answer(ref, t, v)
        assert op.compare(Columns(want), want)[:2] == (0, 0.0)
        b = stale(want)
        assert wr["dst"] not in b.cols["d"] and b.cols["d"].size == want["d"].size - 1
        assert op.compare(b, want)[0] >= 1                   # the newest write is missing
        # what a snapshot from before the write returns is refused too, either way
        assert op.compare(Columns(before), want)[0] >= 1
    f32 = loader.module("controls", "f32").broken(want)
    bad, gap, _ = op.compare(f32, want)
    assert bad == 0 and gap > 1e-9                           # the written f is no float32
    assert stale({"d": np.arange(3), "f": np.ones(3)}) is None    # no w: nothing to tell by
    assert stale([(0, 1)]) is None


def test_the_driver_holds_a_reply_to_the_row_count_of_the_moment():
    op, mix, ref, requests, _ = _run()
    driver = loader.module("drivers", "closed_loop_rw")
    from benchmarks.lib.reply import Reply

    class Session:
        """Acknowledges the write, then answers from the reference; every
        fifth read-back misses its write (a stale read)."""
        def __init__(self):
            self.n = 0

        def execute(self, request):
            before = op.rows_now(request)
            wr = op.next_write(request)
            op.acknowledged(request, wr)
            self.n += 1
            return Reply(n_rows=before if self.n % 5 == 0 else op.rows_now(request))

    recs, last, _, _ = driver.run([Session()], requests, rounds=2)
    assert len(recs) == 64 and sorted(last) == list(range(32))
    stale = [r for i, r in enumerate(recs, 1) if i % 5 == 0]
    # a stale reply of an overwrite has the right COUNT: the check by content catches that one
    assert all(not r.ok for r in stale if r.idx % 4 != 3)
    assert all(r.ok for i, r in enumerate(recs, 1) if i % 5)
    assert requests[0]["rows"] == op.rows_now(requests[0]) - 2   # the list itself stays the seed's


def _ctx(moved, n=10):
    return {"records": [None] * n, "counter": lambda name: moved.get(name, 0)}


def test_readers_divide_by_the_requests_and_have_nothing_to_read_on_the_parent(monkeypatch):
    from nebula_tpu.utils.stats import stats
    read = {m: loader.module("layers", m).read for m in NEW}
    for m in NEW:
        assert loader.module("layers", m).NEEDS
    kept = {"tpu_delta_apply_s.sum": 1.0, "tpu_delta_apply_s.count": 1,
            "tpu_delta_put_s.sum": 1.0, "tpu_delta_fill_ratio": 0.125}
    monkeypatch.setattr(stats(), "snapshot", lambda: dict(kept))
    moved = {"tpu_delta_apply_s.sum": 0.5, "tpu_delta_put_s.sum": 0.02,
             "tpu_delta_keys.sum": 12, "tpu_delta_keys.count": 10, "tpu_pins": 0,
             "write_ack_s.sum": 0.3, "write_ack_s.count": 10}
    got = {m: read[m](_ctx(moved)) for m in NEW}
    assert got == {"delta.apply_ms": 50.0, "delta.put_ms": 2.0, "delta.keys_per_apply": 1.2,
                   "delta.repins_per_req": 0.0, "delta.fill_share": 12.5,
                   "write.ack_ms": pytest.approx(30.0)}
    assert read["delta.repins_per_req"](_ctx(dict(moved, tpu_pins=2))) == 0.2
    # the parent keeps none of the series: every reader leaves its metric out
    monkeypatch.setattr(stats(), "snapshot", lambda: {"tpu_pins": 3})
    assert {m: read[m](_ctx({"tpu_pins": 1})) for m in NEW} == dict.fromkeys(NEW)


# -- against the system, at its small size on the CPU --------------------------


@pytest.fixture()
def deployment():
    """The builder's deployment at the configuration's rehearsal sizes,
    at default flags; `flag` sets `tpu_delta_max_edges` first."""
    from nebula_tpu.utils.config import get_config
    made = []

    def make(seed, flag=None):
        cfg = loader.data("configs", "snb-sf1-rw")
        sizes = cfg["rehearse"]
        mix = loader.data("traffic", "iu8-is3-1s")
        mix["requests"] = sizes["requests"]
        tables = loader.module("reference/generators",
                               cfg["reference"]["generator"]).generate(sizes, seed)
        ref = RefGraph(tables, cfg["reference"]["dedupe_last"])
        requests = make_requests(mix, ref, seed)
        if flag is not None:
            get_config().set_dynamic("tpu_delta_max_edges", flag)
        said = []
        dep = loader.module("builders", cfg["builder"]).build(cfg, sizes, tables, said.append)
        made.append(dep)
        return dep, ref, requests, said
    yield make
    for dep in made:
        dep.close()
    with get_config().lock:
        get_config().dynamic_layer.pop("tpu_delta_max_edges", None)


def test_every_read_back_holds_every_acknowledged_write_at_default_flags(deployment):
    """Three replays of sixteen pairs (36 inserts, 12 overwrites)
    through a LocalCluster at default flags: each read-back equals the
    reference's answer of that moment, no pair pins the graph again."""
    from nebula_tpu.utils.stats import stats
    dep, ref, requests, said = deployment(SEED)
    assert "tpu_pins +0" in said[-1]
    op = op_module("write_read")
    driver = loader.module("drivers", "closed_loop_rw")
    s0 = stats().snapshot()
    checked = []

    class Checked:
        def __init__(self, inner):
            self.inner = inner

        def execute(self, request):
            reply = self.inner.execute(request)
            assert reply.error is None, reply.error
            want = op.answer(ref, request["template"], request["start"])
            checked.append(op.compare(reply, want)[:2])
            return reply

        def close(self):
            self.inner.close()
    session = Checked(dep.open_session())
    try:
        recs, last, _, _ = driver.run([session], requests, rounds=3)
    finally:
        session.close()
    s1 = stats().snapshot()
    assert len(recs) == 48 and all(r.ok for r in recs)
    assert checked == [(0, 0.0)] * 48
    assert sum(op.rows_now(r) - r["rows"] for r in requests) >= 36     # + the probe's, maybe
    assert s1["tpu_pins"] == s0["tpu_pins"]
    assert s1["tpu_repin_avoided"] - s0["tpu_repin_avoided"] == 48
    assert s1["tpu_delta_keys.sum"] - s0["tpu_delta_keys.sum"] == 48
    assert s1["write_ack_s.count"] - s0["write_ack_s.count"] == 48
    assert s1.get("tpu_host_fallback", 0) == s0.get("tpu_host_fallback", 0)
    assert 0 < s1["tpu_delta_fill_ratio"] < 0.75


def test_the_builder_refuses_a_program_that_serves_the_read_back_by_a_re_pin(deployment):
    """What the parent does (its delta plane is off at default flags),
    made here with the flag's explicit 0: the probe pair pins the graph
    again, and the builder says so and exits non-zero before any
    warm-up."""
    with pytest.raises(SystemExit) as gone:
        deployment(SEED + 1, flag=0)
    assert gone.value.code not in (0, None) and "re-export" in str(gone.value.code)
