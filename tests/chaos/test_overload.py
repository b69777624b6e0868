"""Overload chaos schedules (ISSUE 10): saturation + faults over a
live 3-replica cluster, with the admission plane armed.

The acceptance claims under test:

  * shedding NEVER drops acked work — the PR5 invariants (acked writes
    exactly-once, byte-identical replica convergence, drained TOSS
    journals) hold through an overload storm, with and without real
    faults underneath;
  * every E_OVERLOAD that surfaces to the client carries a
    machine-parseable retry-after hint;
  * control statements (SHOW QUERIES) keep answering during
    saturation — the priority lane's proof.

Marked `chaos` + `slow`: NOT part of the tier-1 gate.  The
deadline-eviction and kill-eviction contracts are unit tests
(tests/unit/test_admission.py); a goodput curve under overload is the
benchmark's to draw (ROADMAP B7: an open-loop driver).
"""
import threading
import time

import pytest

from nebula_tpu.utils.admission import (admission, is_overload,
                                        parse_retry_after)
from nebula_tpu.utils.config import get_config
from nebula_tpu.utils.failpoints import FaultSchedule, fail
from nebula_tpu.utils.stats import stats

from harness import ChaosCluster, WriteLedger, assert_acked_exactly_once

pytestmark = [pytest.mark.chaos, pytest.mark.slow]

_FLAGS = ("max_running_queries", "admission_queue_capacity",
          "rpc_server_inbox_capacity", "query_timeout_secs")


def _arm_admission(slots=3, capacity=4, timeout_s=15.0):
    get_config().set_dynamic_many({
        "max_running_queries": slots,
        "admission_queue_capacity": capacity,
        "query_timeout_secs": timeout_s,
    })


def _disarm_admission():
    cfg = get_config()
    with cfg.lock:
        for k in _FLAGS:
            cfg.dynamic_layer.pop(k, None)
    admission().reset()


def _overload_storm(cc, n_writers=10, writes_each=10, vid_base=1000):
    """Concurrent single-vertex INSERT storm (each writer on its own
    client/session) + a control probe issuing SHOW QUERIES throughout.
    Returns (ledger, sheds, control_errors, control_count)."""
    led = WriteLedger()
    sheds, shed_lock = [], threading.Lock()

    def writer(wid):
        cl = cc.cluster.client()
        try:
            cl.execute(f"USE {cc.space}")
            for k in range(writes_each):
                vid = vid_base + wid * 1000 + k
                age = (wid * 7 + k) % 90 + 1
                r = cl.execute(
                    f'INSERT VERTEX Person(name, age) VALUES '
                    f'{vid}:("p{vid}",{age})')
                if r.error is None:
                    led.ack(vid, {"age": age})
                elif is_overload(r.error):
                    with shed_lock:
                        sheds.append(r.error)
                else:
                    led.fail(vid, r.error)
        finally:
            cl.close()

    ctl_errs, ctl_n = [], [0]
    stop = threading.Event()

    def control():
        cl = cc.cluster.client()
        try:
            cl.execute(f"USE {cc.space}")
            while not stop.wait(0.05):
                r = cl.execute("SHOW QUERIES")
                ctl_n[0] += 1
                if r.error is not None:
                    ctl_errs.append(r.error)
        finally:
            cl.close()

    ths = [threading.Thread(target=writer, args=(i,), daemon=True)
           for i in range(n_writers)]
    ctl_t = threading.Thread(target=control, daemon=True)
    ctl_t.start()
    for t in ths:
        t.start()
    for t in ths:
        t.join(120)
    stop.set()
    ctl_t.join(10)
    return led, sheds, ctl_errs, ctl_n[0]


def test_overload_storm_invariants(tmp_path):
    """Pure saturation (no injected faults): 10 writers against 3
    admission slots / queue of 4.  The plane must ENGAGE (statements
    queued), control statements must answer throughout, surfaced sheds
    must carry hints, and the acked set must survive exactly-once with
    replicas byte-identical."""
    cc = ChaosCluster(data_dir=str(tmp_path))
    try:
        _arm_admission(slots=3, capacity=4)
        enq0 = stats().snapshot().get("admission_enqueued", 0)
        led, sheds, ctl_errs, ctl_n = _overload_storm(cc)
        assert ctl_n > 0 and not ctl_errs, \
            f"control lane failed during saturation: {ctl_errs[:3]}"
        for e in sheds:
            assert parse_retry_after(e) is not None, e
        assert stats().snapshot().get("admission_enqueued", 0) > enq0, \
            "the storm never engaged the admission queue"
        assert led.acked, "nothing acked — storm misconfigured"
        _disarm_admission()
        cc.wait_no_pending_chains()
        cc.wait_replicas_converged(require=3)
        assert_acked_exactly_once(cc, led)
    finally:
        _disarm_admission()
        cc.stop()


def test_overload_storm_with_faults_keeps_acked_writes(tmp_path):
    """Saturation + real faults underneath (WAL fsync stalls slowing
    the data plane, acked-write replies killed at random): shedding and
    the exactly-once machinery must compose — every acked write
    survives exactly once, replicas converge byte-identically."""
    cc = ChaosCluster(data_dir=str(tmp_path))
    try:
        _arm_admission(slots=3, capacity=4, timeout_s=25.0)
        sched = FaultSchedule(707, [
            {"fp": "wal:pre_fsync", "action": "delay", "arg": 0.06,
             "p": 0.3, "key": "storage", "max": 30},
            {"fp": "rpc:server_reply", "action": "raise", "p": 0.25,
             "key": "storage.write|ok", "max": 5},
        ]).arm(fail)
        led, sheds, ctl_errs, ctl_n = _overload_storm(
            cc, n_writers=8, writes_each=8, vid_base=50_000)
        sched.disarm(fail)
        assert ctl_n > 0 and not ctl_errs, \
            f"control lane failed during saturation: {ctl_errs[:3]}"
        for e in sheds:
            assert parse_retry_after(e) is not None, e
        assert led.acked
        _disarm_admission()
        cc.wait_no_pending_chains()
        cc.wait_replicas_converged(require=3)
        assert_acked_exactly_once(cc, led)
        # faults demonstrably fired — the run exercised overload UNDER
        # failure, not beside it (the seed pins the trigger stream)
        assert sum(sched.fired.values()) > 0, sched.fired
    finally:
        fail.reset()
        _disarm_admission()
        cc.stop()


def test_overload_storm_with_leader_kill(tmp_path):
    """Saturation + a hard storaged kill mid-storm: the replica walk
    re-homes writes while admission keeps the herd bounded; acked
    writes survive exactly once on the remaining replicas."""
    cc = ChaosCluster(data_dir=str(tmp_path))
    try:
        _arm_admission(slots=3, capacity=6, timeout_s=30.0)
        killed = threading.Event()

        def killer():
            time.sleep(1.0)       # let the storm saturate first
            cc.kill_storaged(cc.leader_of_most_parts())
            killed.set()

        kt = threading.Thread(target=killer, daemon=True)
        kt.start()
        led, sheds, ctl_errs, ctl_n = _overload_storm(
            cc, n_writers=8, writes_each=8, vid_base=80_000)
        kt.join(30)
        assert killed.is_set()
        for e in sheds:
            assert parse_retry_after(e) is not None, e
        assert led.acked
        assert ctl_n > 0, "control probe never ran"
        _disarm_admission()
        cc.wait_no_pending_chains()
        cc.wait_replicas_converged(require=2)
        assert_acked_exactly_once(cc, led)
    finally:
        _disarm_admission()
        cc.stop()
