"""The collective-launch mutex on the clock (PR 28): every launch of a
sharded program observes what it waited for `_launch_mutex` into the
series `tpu_collective_wait_s` and, inside a statement's trace, opens a
`device:launch_wait` span (phase `queue`).  Local mode takes no mutex and
observes nothing.  The mutex itself is as it was: still one sharded
program at a time."""
from __future__ import annotations

import threading
import time

import pytest

from nebula_tpu.core import expr as E
from nebula_tpu.utils import trace
from nebula_tpu.utils.stats import stats

SERIES = "tpu_collective_wait_s"


def _waits():
    snap = stats().snapshot()
    return snap.get(SERIES + ".count", 0), snap.get(SERIES + ".sum", 0.0)


@pytest.fixture(scope="module")
def pinned(pinned_pair):
    """The same 4-part graph pinned on a 4-device mesh and on one device."""
    yield from pinned_pair("waits", 28)


def _go(rt, store, v):
    rows, st = rt.traverse(store, "waits", [v], ["E"], "out", 3, yields=[
        (E.FunctionCall("dst", [E.EdgeExpr()]), "d"), (E.EdgeProp("E", "w"), "w")])
    return len(rows), st


def test_local_mode_observes_no_wait(pinned):
    mesh, local, store = pinned
    assert local.local_mode and not mesh.local_mode
    n0, s0 = _waits()
    assert _go(local, store, 5)[0] > 0
    assert _waits() == (n0, s0)


def test_every_sharded_launch_observes_its_wait(pinned):
    mesh, _local, store = pinned
    _go(mesh, store, 5)                                   # compile outside the count
    n0, s0 = _waits()
    rows, st = _go(mesh, store, 5)
    n1, s1 = _waits()
    assert rows > 0 and st.shards == 4
    assert n1 - n0 >= 2                                   # the seed put and the kernel run
    assert 0 <= s1 - s0 < 0.5                             # nobody to wait for


def test_a_second_session_waits_for_the_first(pinned):
    """One thread holds the mutex as a running sharded program does; a
    statement's launch waits for it, and the series says for how long."""
    mesh, _local, store = pinned
    _go(mesh, store, 7)
    n0, s0 = _waits()
    held, release = threading.Event(), threading.Event()

    def holder():
        with mesh._collective_launch():
            held.set()
            release.wait(5)

    t = threading.Thread(target=holder)
    t.start()
    assert held.wait(5)
    threading.Timer(0.15, release.set).start()
    t0 = time.perf_counter()
    rows, _st = _go(mesh, store, 7)
    took = time.perf_counter() - t0
    t.join()
    n1, s1 = _waits()
    assert rows > 0 and took >= 0.1
    assert n1 - n0 >= 3                                   # the holder's own launch counts too
    assert 0.1 <= s1 - s0 <= took + 0.01                  # the statement waited out the holder
    assert not mesh._launch_mutex.locked()


def test_the_wait_is_a_span_of_phase_queue_inside_a_trace(pinned):
    mesh, local, store = pinned
    assert trace.phase_of("device:launch_wait") == "queue"
    with trace.start_trace("test:launch_wait") as root:
        _go(mesh, store, 9)
    spans = trace.trace_store().get(root.trace_id)["spans"]
    names = [s["name"] for s in spans]
    assert names.count("device:launch_wait") >= 2
    by_sid = {s["sid"]: s for s in spans}
    parents = {by_sid[s["psid"]]["name"] for s in spans if s["name"] == "device:launch_wait"}
    assert parents <= {"device:put", "device:dispatch"} and parents
    with trace.start_trace("test:launch_wait_local") as root:
        _go(local, store, 9)
    assert "device:launch_wait" not in [
        s["name"] for s in trace.trace_store().get(root.trace_id)["spans"]]
