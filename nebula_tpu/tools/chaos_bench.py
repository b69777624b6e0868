"""chaos-bench — seeded fault-schedule runner over a live LocalCluster
(ISSUE 5).

Each schedule arms a deterministic `FaultSchedule` (utils/failpoints:
every trigger decision is drawn from `random.Random(f"{seed}:{site}")`)
over a live 3-replica cluster, drives a seeded workload through the
public client, then measures what the robustness layer actually paid:

  recovery_s            faults stop → every part's live replicas export
                        byte-identical state and all TOSS journals drain
  retry_amplification   internal re-sends per acked statement
                        (replica-walk + RPC-client retries + meta leader
                        walks, from the deterministic counters)
  dedup_hits            re-sent writes answered from the exactly-once
                        window instead of double-applying

and re-asserts the chaos invariants (acked writes exactly once,
replicas converged) — a schedule that breaks them FAILS and prints a
one-line reproducer:

    REPRODUCE: python -m nebula_tpu.tools.chaos_bench --schedule <name> --seed <n>

The pytest twin of any failure is `tests/chaos/test_schedules.py` with
the same seed.  Usage:

    python -m nebula_tpu.tools.chaos_bench                 # all schedules
    python -m nebula_tpu.tools.chaos_bench --schedule reply_loss --seed 606

Emits one JSON object on stdout (CI-diffable).
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import threading
import time

# the harness lives with the chaos tests (it IS test infrastructure —
# this tool is its headless runner); resolve it relative to the repo
_REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
_CHAOS_DIR = os.path.join(_REPO, "tests", "chaos")
if _CHAOS_DIR not in sys.path:
    sys.path.insert(0, _CHAOS_DIR)

#: schedule → default seed (the ones the pytest twins pin)
DEFAULT_SEEDS = {
    "leader_kill": 101,
    "fsync_stall": 202,
    "torn_toss": 303,
    "meta_partition": 404,
    "reply_loss": 606,
}


def _counters():
    from nebula_tpu.utils.stats import stats
    snap = stats().snapshot()

    def total(prefix):
        return sum(v for k, v in snap.items() if k.startswith(prefix))

    return {
        "replica_walk_retries": total("storage_replica_walk_retries"),
        "rpc_client_retries": total("rpc_client_retries"),
        "meta_leader_walk_retries": snap.get("meta_leader_walk_retries", 0),
        "breaker_trips": snap.get("rpc_breaker_trips", 0),
        "breaker_short_circuits": snap.get("rpc_breaker_short_circuits", 0),
        "dedup_hits": snap.get("storage_write_dedup_hits", 0)
        + snap.get("storage_write_dedup_apply_skips", 0),
        "failpoints_fired": total("failpoint_fired"),
    }


def _settle(cc, require: int) -> float:
    """Seconds for the cluster to prove itself healthy again: replicas
    byte-identical + TOSS journals drained."""
    t0 = time.perf_counter()
    cc.wait_no_pending_chains()
    cc.wait_replicas_converged(require=require)
    return time.perf_counter() - t0


def _finish(cc, led, seed, fired, require: int) -> dict:
    from harness import assert_acked_exactly_once
    recovery_s = _settle(cc, require)
    assert_acked_exactly_once(cc, led)
    c = _counters()
    acked = len(led.acked)
    retries = (c["replica_walk_retries"] + c["rpc_client_retries"]
               + c["meta_leader_walk_retries"])
    return {
        "seed": seed,
        "acked": acked,
        "failed": len(led.failed),
        "faults_fired": fired,
        "recovery_s": round(recovery_s, 3),
        "retries": retries,
        "retry_amplification": round(retries / acked, 3) if acked else None,
        "counters": c,
        "invariants_ok": True,
    }


# -- schedules --------------------------------------------------------------


def sched_leader_kill(seed: int, writes: int) -> dict:
    """Hard-kill the storaged leading the most parts mid-workload; the
    tokened replica-walk retry must carry every statement through."""
    from harness import ChaosCluster
    tmp = tempfile.mkdtemp(prefix="chaos_bench_")
    cc = ChaosCluster(data_dir=tmp)
    try:
        half = threading.Event()
        led_box = {}

        def drive():
            # the workload thread flags the halfway point itself (vid
            # order is the seeded schedule, so "halfway" is data-
            # deterministic even though the kill lands asynchronously)
            from harness import WriteLedger
            led = WriteLedger()
            import random as _r
            rng = _r.Random(seed)
            for k in range(writes):
                vid = 1000 + k
                age = rng.randint(1, 99)
                r = cc.run(f'INSERT VERTEX Person(name, age) VALUES '
                           f'{vid}:("p{vid}",{age})')
                (led.ack(vid, {"age": age}) if r.error is None
                 else led.fail(vid, r.error))
                if k == writes // 2:
                    half.set()
            led_box["led"] = led

        t = threading.Thread(target=drive)
        t.start()
        half.wait(60.0)
        t_kill = time.perf_counter()
        cc.kill_storaged(cc.leader_of_most_parts())
        t.join()
        res = _finish(cc, led_box["led"], seed, 1, require=2)
        res["kill_to_drained_s"] = round(time.perf_counter() - t_kill, 3)
        return res
    finally:
        cc.stop()
        shutil.rmtree(tmp, ignore_errors=True)


def sched_fsync_stall(seed: int, writes: int) -> dict:
    """Random 80ms WAL fsync stalls on the storage plane."""
    from nebula_tpu.utils.failpoints import FaultSchedule, fail
    from harness import ChaosCluster, mixed_workload
    tmp = tempfile.mkdtemp(prefix="chaos_bench_")
    cc = ChaosCluster(data_dir=tmp)
    try:
        sched = FaultSchedule(seed, [
            {"fp": "wal:pre_fsync", "action": "delay", "arg": 0.08,
             "p": 0.35, "key": "storage", "max": 25},
        ]).arm(fail)
        led = mixed_workload(cc, seed=seed, n_writes=writes)
        sched.disarm(fail)
        return _finish(cc, led, seed, sum(sched.fired.values()), require=3)
    finally:
        cc.stop()
        shutil.rmtree(tmp, ignore_errors=True)


def sched_torn_toss(seed: int, writes: int) -> dict:
    """Tear TOSS chains between the journaled out-half and the in-half;
    the janitor must re-drive every journal (failed statements allowed,
    torn state not)."""
    from nebula_tpu.utils.failpoints import FaultSchedule, fail
    from harness import ChaosCluster, WriteLedger
    tmp = tempfile.mkdtemp(prefix="chaos_bench_")
    cc = ChaosCluster(data_dir=tmp)
    try:
        n = max(writes // 2, 10)
        for k in range(n):
            cc.ok(f'INSERT VERTEX Person(name, age) VALUES '
                  f'{9000 + k}:("t{k}",{k % 90 + 1})')
        sched = FaultSchedule(seed, [
            {"fp": "toss:pre_in", "action": "raise", "p": 0.5, "max": 4},
        ]).arm(fail)
        led = WriteLedger()
        for k in range(n):
            s, d = 9000 + k, 9000 + (k + 1) % n
            r = cc.run(f"INSERT EDGE KNOWS(w) VALUES {s}->{d}:({k})")
            # edge acks ride the same exactly-once invariant through the
            # ledger's vertex probe; torn statements may legally fail
            if r.error is not None:
                led.fail(s, r.error)
        sched.disarm(fail)
        for k in range(n):
            led.ack(9000 + k, {"age": k % 90 + 1})
        return _finish(cc, led, seed, sum(sched.fired.values()), require=3)
    finally:
        cc.stop()
        shutil.rmtree(tmp, ignore_errors=True)


def sched_meta_partition(seed: int, writes: int) -> dict:
    """3-metad quorum with half its replication rounds dropped."""
    from nebula_tpu.utils.failpoints import FaultSchedule, fail
    from harness import ChaosCluster, mixed_workload
    tmp = tempfile.mkdtemp(prefix="chaos_bench_")
    cc = ChaosCluster(n_meta=3, data_dir=tmp)
    try:
        sched = FaultSchedule(seed, [
            {"fp": "raft:replicate", "action": "raise", "p": 0.5,
             "key": "meta", "max": 60},
        ]).arm(fail)
        led = mixed_workload(cc, seed=seed, n_writes=writes,
                             vid_base=2000)
        sched.disarm(fail)
        return _finish(cc, led, seed, sum(sched.fired.values()), require=3)
    finally:
        cc.stop()
        shutil.rmtree(tmp, ignore_errors=True)


def sched_reply_loss(seed: int, writes: int) -> dict:
    """Kill acked storage.write replies at random — the dedup window's
    home turf; re-sends must land exactly once."""
    from nebula_tpu.utils.failpoints import FaultSchedule, fail
    from harness import ChaosCluster, mixed_workload
    tmp = tempfile.mkdtemp(prefix="chaos_bench_")
    cc = ChaosCluster(data_dir=tmp)
    try:
        sched = FaultSchedule(seed, [
            {"fp": "rpc:server_reply", "action": "raise", "p": 0.4,
             "key": "storage.write|ok", "max": 8},
        ]).arm(fail)
        led = mixed_workload(cc, seed=seed, n_writes=writes,
                             vid_base=3000)
        sched.disarm(fail)
        res = _finish(cc, led, seed, sum(sched.fired.values()), require=3)
        if sum(sched.fired.values()) and not res["counters"]["dedup_hits"]:
            raise AssertionError("replies were killed but no re-send "
                                 "was deduplicated")
        return res
    finally:
        cc.stop()
        shutil.rmtree(tmp, ignore_errors=True)


SCHEDULES = {
    "leader_kill": sched_leader_kill,
    "fsync_stall": sched_fsync_stall,
    "torn_toss": sched_torn_toss,
    "meta_partition": sched_meta_partition,
    "reply_loss": sched_reply_loss,
}


def run(schedules=None, seed=None, writes: int = 40) -> dict:
    """Run the named schedules (default: all); returns per-schedule
    metrics plus their aggregate (`fault_recovery`).
    A broken invariant raises AFTER printing its reproducer line."""
    names = list(schedules or SCHEDULES)
    out = {"writes_per_schedule": writes, "schedules": {}}
    worst_recovery = 0.0
    total_retries = total_acked = 0
    for name in names:
        s = seed if seed is not None else DEFAULT_SEEDS[name]
        try:
            r = SCHEDULES[name](s, writes)
        except Exception:
            print(f"REPRODUCE: python -m nebula_tpu.tools.chaos_bench "
                  f"--schedule {name} --seed {s}", file=sys.stderr,
                  flush=True)
            raise
        out["schedules"][name] = r
        worst_recovery = max(worst_recovery, r["recovery_s"])
        total_retries += r["retries"]
        total_acked += r["acked"]
    out["worst_recovery_s"] = round(worst_recovery, 3)
    out["retry_amplification"] = (round(total_retries / total_acked, 3)
                                  if total_acked else None)
    out["invariants_ok"] = True
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--schedule", action="append",
                    choices=sorted(SCHEDULES),
                    help="schedule(s) to run (default: all)")
    ap.add_argument("--seed", type=int, default=None,
                    help="override the schedule's pinned seed")
    ap.add_argument("--writes", type=int, default=40,
                    help="workload statements per schedule")
    args = ap.parse_args(argv)
    print(json.dumps(run(args.schedule, args.seed, args.writes),
                     indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
