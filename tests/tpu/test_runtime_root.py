"""One trace per device statement wherever it enters (PR 39): a statement
that reaches `TpuRuntime.traverse` / `traverse_hops` / `bfs` with no trace
active is rooted by the runtime's own entry (`query:tpu.<entry>`), so the
spans below it are live, fold into the phase ledger and reach
`trace_store()`; under an active context, or with `enable_query_tracing`
off, nothing is opened.  And the spans PR 39 added for what the root would
otherwise keep as its own time are in the tree."""
from __future__ import annotations

import threading

import numpy as np
import pytest

from nebula_tpu.core import expr as E
from nebula_tpu.utils import trace
from nebula_tpu.utils.config import get_config
from nebula_tpu.utils.stats import stats
from nebula_tpu.utils.workload import live_registry

SPACE = "rooted"
YIELDS = [(E.FunctionCall("dst", [E.EdgeExpr()]), "d"), (E.EdgeProp("E", "w"), "w"),
          (E.EdgeProp("E", "f"), "f")]
# what PR 39 added below the entry, on the path of a GO with yields
ADDED = {"tpu:prep", "tpu:launch", "tpu:seed_prep", "tpu:launch_account",
         "device:fetch.rows", "device:release", "device:materialise.concat",
         "device:materialise.decode"}


@pytest.fixture(scope="module")
def pinned(pinned_pair):
    """The shared graph on a 4-device mesh and on one device, every
    program a GO with these yields needs compiled outside the tests."""
    for mesh, local, store in pinned_pair(SPACE, 39):
        for rt in (mesh, local):
            go(rt, store, 1)
        yield mesh, local, store


def go(rt, store, seed):
    rows, _ = rt.traverse(store, SPACE, [seed], ["E"], "out", 3, yields=YIELDS)
    return rows


class Since:
    """What the trace store and the phase ledger gained inside the block."""

    def __enter__(self):
        self._tids = {t["tid"] for t in trace.trace_store().list(limit=1000)}
        self._c0 = stats().snapshot()
        return self

    def __exit__(self, *exc):
        self.traces = [trace.trace_store().get(t["tid"])
                       for t in trace.trace_store().list(limit=1000)
                       if t["tid"] not in self._tids]
        c1 = stats().snapshot()
        self.phases = {k: v - self._c0.get(k, 0) for k, v in c1.items()
                       if k.startswith("stmt_phase_") and v != self._c0.get(k, 0)}
        return False


def names(entry):
    return [s["name"] for s in entry["spans"]]


@pytest.mark.parametrize("which", ["mesh", "local"])
def test_a_direct_traverse_leaves_one_trace_that_closes(pinned, which):
    mesh, local, store = pinned
    rt = mesh if which == "mesh" else local
    shares = []
    for seed in (3, 4, 5):
        with Since() as got:
            assert len(go(rt, store, seed)) > 0
        assert [t["name"] for t in got.traces] == ["query:tpu.traverse"]
        entry = got.traces[0]
        root = next(s for s in entry["spans"] if not s["psid"])
        assert root["svc"] == "tpu" and root["attrs"]["space"] == SPACE
        assert ADDED <= set(names(entry)), ADDED - set(names(entry))
        assert {"device:queue", "device:put", "device:dispatch", "device:fetch",
                "device:materialise", "tpu:snapshot_check"} <= set(names(entry))
        assert ("device:launch_wait" in names(entry)) == (which == "mesh")
        # the budget closes, and the ledger took exactly this one root
        us, n = trace.fold_phases(entry["spans"])
        assert abs(sum(us.values()) - root["dur_us"]) <= len(trace.PHASES)
        assert got.phases["stmt_phase_n{phase=other}"] == 1
        for ph in ("release", "mat_concat", "mat_decode", "materialise", "fetch", "exec"):
            assert got.phases[f"stmt_phase_n{{phase={ph}}}"] == n[ph] >= 1
        assert sum(v for k, v in got.phases.items() if k.startswith("stmt_phase_us")) \
            == sum(us.values())
        shares.append(us["other"] / root["dur_us"])
        # the launch's phases lie inside `tpu:launch`, the pieces of row
        # assembly inside `device:materialise`
        by_id = {s["sid"]: s for s in entry["spans"]}
        for s in entry["spans"]:
            if s["name"] in ("tpu:seed_prep", "device:release", "tpu:launch_account",
                             "device:fetch.rows", "device:put"):
                assert by_id[s["psid"]]["name"] == "tpu:launch"
            if s["name"].startswith("device:materialise."):
                assert by_id[s["psid"]]["name"] == "device:materialise"
    # what the root keeps to itself: under a tenth (a few per cent here)
    assert min(shares) < 0.1, shares


def test_the_spans_of_phase_fetch_cover_the_fetchs_own_clock(pinned):
    _, local, store = pinned
    with Since() as got:
        _, st = local.traverse(store, SPACE, [9], ["E"], "out", 3, yields=YIELDS)
    entry = got.traces[0]
    by_id = {s["sid"]: s for s in entry["spans"]}
    top = [s for s in entry["spans"] if s["name"].startswith("device:fetch")
           and by_id[s["psid"]]["name"] == "tpu:launch"]
    # `device:fetch` (the transfer with the taker's set-up), then
    # `device:fetch.rows` (the capture's own transfer nested in it)
    assert [s["name"] for s in top] == ["device:fetch", "device:fetch.rows"]
    covered_us = sum(s["dur_us"] for s in top)
    assert covered_us <= st.fetch_s * 1e6 + 2
    assert covered_us >= 0.8 * st.fetch_s * 1e6, (covered_us, st.fetch_s)


def test_under_an_active_context_no_second_root_opens(pinned):
    mesh, _, store = pinned
    with Since() as got:
        with trace.start_trace("test:caller"):
            assert len(go(mesh, store, 6)) > 0
    assert [t["name"] for t in got.traces] == ["test:caller"]
    inner = names(got.traces[0])
    assert ADDED <= set(inner) and not any(n.startswith("query:") for n in inner)
    assert got.phases == {}                  # not a statement's root: nothing folded


def test_with_query_tracing_off_nothing_opens_and_nothing_is_recorded(pinned):
    mesh, _, store = pinned
    cfg = get_config()
    cfg.set_dynamic_many({"enable_query_tracing": False})
    try:
        with Since() as got:
            rows = go(mesh, store, 7)
    finally:
        with cfg.lock:
            cfg.dynamic_layer.pop("enable_query_tracing", None)
    assert len(rows) > 0 and got.traces == [] and got.phases == {}


def test_a_retried_statement_stays_one_trace(pinned, monkeypatch):
    from nebula_tpu.tpu.device import SnapshotRetired
    mesh, _, store = pinned
    real, calls = mesh._escalate_locked, []

    def retired_once(*a, **kw):
        calls.append(1)
        if len(calls) == 1:
            raise SnapshotRetired("swapped under the statement")
        return real(*a, **kw)
    monkeypatch.setattr(mesh, "_escalate_locked", retired_once)
    r0 = stats().snapshot().get("tpu_stmt_retired_retries", 0)
    with Since() as got:
        assert len(go(mesh, store, 8)) > 0
    assert stats().snapshot()["tpu_stmt_retired_retries"] == r0 + 1 and len(calls) == 2
    assert [t["name"] for t in got.traces] == ["query:tpu.traverse"]
    assert names(got.traces[0]).count("tpu:launch") == 2      # both attempts, one tree
    assert got.phases["stmt_phase_n{phase=other}"] == 1


@pytest.mark.parametrize("entry,kernel", [("traverse_hops", "hops"), ("bfs", "bfs")])
def test_the_other_entries_root_themselves_too(pinned, entry, kernel):
    mesh, _, store = pinned
    with Since() as got:
        if entry == "bfs":
            dist, _ = mesh.bfs(store, SPACE, [3], ["E"], "out", 3)
            assert (np.asarray(dist) >= 0).sum() > 1
        else:
            frames, _ = mesh.traverse_hops(store, SPACE, [3], ["E"], "out", 2)
            assert sum(f.n for f in frames) > 0
    assert [t["name"] for t in got.traces] == [f"query:tpu.{entry}"]
    spans = got.traces[0]["spans"]
    launch = next(s for s in spans if s["name"] == "tpu:launch")
    assert launch["attrs"]["kernel"] == kernel
    assert {"tpu:prep", "tpu:seed_prep", "device:release", "tpu:launch_account"} \
        <= {s["name"] for s in spans}
    root = next(s for s in spans if not s["psid"])
    us, _ = trace.fold_phases(spans)
    assert abs(sum(us.values()) - root["dur_us"]) <= len(trace.PHASES)


def test_a_lane_batched_members_trace_holds_the_replayed_phases(pinned):
    """Two statements share ONE launch: nothing is traced on the
    launcher's thread while it runs, and each member's own root takes the
    launch's phases by replay, the new ones among them."""
    from nebula_tpu.tpu.batch import batch_former
    _, local, store = pinned
    cfg = get_config()
    batch_former().reset()
    # evidence of company, so that the former opens its window at all
    regs = [live_registry().register(qid=q, session=0, user="t", stmt="dummy", kind="Go")
            for q in (-391, -392)]
    cfg.set_dynamic_many({"batch_max_lanes": 8, "batch_wait_us": 400_000})
    out, errs = {}, []

    def member(seed):
        try:
            out[seed] = len(go(local, store, seed))
        except Exception as ex:  # noqa: BLE001 — reported below
            errs.append(repr(ex))
    try:
        f0 = stats().snapshot().get("tpu_batches_formed", 0)
        with Since() as got:
            threads = [threading.Thread(target=member, args=(s,), daemon=True)
                       for s in (11, 12)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(120)
            assert not any(t.is_alive() for t in threads)
        assert not errs and all(v > 0 for v in out.values()), (errs, out)
        assert stats().snapshot().get("tpu_batches_formed", 0) == f0 + 1
    finally:
        with cfg.lock:
            for k in ("batch_max_lanes", "batch_wait_us"):
                cfg.dynamic_layer.pop(k, None)
        for q, r in zip((-391, -392), regs):
            if r is not None:
                live_registry().deregister(q)
        batch_former().reset()
    assert sorted(t["name"] for t in got.traces) == ["query:tpu.traverse"] * 2
    assert got.phases["stmt_phase_n{phase=other}"] == 2
    for entry in got.traces:
        spans = entry["spans"]
        queue = [s for s in spans if s["name"] == "device:queue"]
        assert len(queue) == 1 and queue[0]["attrs"]["lanes"] == 2     # the replayed one
        have = [s["name"] for s in spans]
        for name in ("tpu:seed_prep", "device:put", "device:dispatch", "device:fetch",
                     "device:fetch.rows", "device:release", "tpu:launch_account"):
            assert name in have, (name, have)
        # replayed under the member's own `tpu:launch`, and the budget closes
        by_id = {s["sid"]: s for s in spans}
        assert by_id[next(s for s in spans if s["name"] == "device:release")["psid"]][
            "name"] == "tpu:launch"
        root = next(s for s in spans if not s["psid"])
        us, _ = trace.fold_phases(spans)
        assert abs(sum(us.values()) - root["dur_us"]) <= len(trace.PHASES)
        assert us["release"] >= 0 and "fetch" in us
