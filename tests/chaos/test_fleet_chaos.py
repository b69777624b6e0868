"""Fleet failover chaos (ISSUE 20): kill 1-of-3 graphds under mixed
read/write load with result caches armed fleet-wide.

The acceptance claims under test:

  * ZERO wrong rows — every value a reader observes is one a writer
    actually wrote, and per-coordinator observations never regress
    (cluster cache epochs: retired keys are unreachable, a coordinator
    never re-serves an older cached value for a key it already
    advanced past);
  * acked-exactly-once through the crash — every acked write is
    present with its acked value afterwards; an unknown-outcome
    E_COORDINATOR_LOST write is resolved by read-then-retry, never by
    a blind re-send;
  * ZERO stale cross-coordinator cache hits once the bounded
    propagation window closes — cached reads on EVERY surviving
    coordinator converge to the final acked values, and the
    time-to-coherence is measured and bounded;
  * failover recovery is bounded — the client homed on the killed
    coordinator completes its next statement within seconds, not
    deadline-timeouts.

Marked `chaos` + `slow`: NOT part of the tier-1 gate.  The
aggressor-tenant DWRR share is a unit contract
(tests/unit/test_fleet.py); a fleet goodput curve is the benchmark's
to draw (ROADMAP B7).
"""
import threading
import time

import pytest

from nebula_tpu.cluster.client import GraphClient
from nebula_tpu.utils.config import get_config
from nebula_tpu.utils.stats import stats

from harness import ChaosCluster

pytestmark = [pytest.mark.chaos, pytest.mark.slow]

_FLAGS = ("result_cache_size", "result_cache_strict_epoch")


def _pop_flags():
    cfg = get_config()
    for k in _FLAGS:
        cfg.dynamic_layer.pop(k, None)


def _fleet_client(cc, home: int) -> GraphClient:
    """A failover client HOMED on graphd `home` (endpoint rotation puts
    it first) — so killing that graphd exercises this client's
    failover, not just its siblings'."""
    addrs = cc.cluster.graph_addrs
    c = GraphClient(addrs[home:] + addrs[:home])
    c.authenticate("root", "nebula")
    r = c.execute(f"USE {cc.space}")
    assert r.error is None, r.error
    return c


def _resolve_write(client, vid: int, val: int) -> bool:
    """Drive one UPDATE to a definite outcome: an unknown-outcome
    E_COORDINATOR_LOST is resolved by reading back (reads retry
    safely) and re-sending ONLY when provably not applied.  Returns
    whether the write is acked-with-val."""
    for _ in range(6):
        r = client.execute(f"UPDATE VERTEX ON Person {vid} SET age = {val}")
        if r.error is None:
            return True
        if "E_COORDINATOR_LOST" not in r.error:
            return False
        rr = client.execute(
            f"FETCH PROP ON Person {vid} YIELD Person.age AS a")
        if rr.error is None and rr.data.rows \
                and int(rr.data.rows[0][0]) >= val:
            return True                    # it DID land before the crash
        # provably behind: safe to drive again
    return False


def test_kill_one_of_three_graphds_under_load():
    cc = ChaosCluster(n_meta=1, n_storage=3, n_graph=3, parts=4,
                      replica_factor=3)
    get_config().set_dynamic("result_cache_size", 128)
    get_config().set_dynamic("result_cache_strict_epoch", True)
    victim = 2                      # graphd 0 stays up for the harness
    rounds, per_writer = 5, 20
    ranges = {w: list(range(2000 + w * 100, 2000 + w * 100 + per_writer))
              for w in range(3)}
    acked = {}                      # vid -> highest acked val
    acked_lock = threading.Lock()
    wrong = []                      # (who, vid, saw, context)
    recovery = {}                   # box for the victim writer's measure
    stop_readers = threading.Event()
    kill_at = threading.Barrier(3 + 1, timeout=60)   # 3 writers + main
    try:
        # seed every vid through the stable coordinator
        for vids in ranges.values():
            for v in vids:
                cc.ok(f'INSERT VERTEX Person(name, age) VALUES '
                      f'{v}:("p{v}",0)')
                with acked_lock:
                    acked[v] = 0

        def writer(w):
            client = _fleet_client(cc, home=w)
            for rnd in range(1, rounds + 1):
                if rnd == 3:
                    kill_at.wait()          # main kills graphd `victim`
                    if w == victim:
                        t0 = time.monotonic()
                for v in ranges[w]:
                    if _resolve_write(client, v, rnd):
                        with acked_lock:
                            acked[v] = max(acked[v], rnd)
                    else:
                        wrong.append(("writer", v, rnd, "unresolved"))
                if rnd == 3 and w == victim:
                    recovery["failover_s"] = time.monotonic() - t0
            client.close()

        def reader(rid):
            client = _fleet_client(cc, home=rid)   # homed 0 and 1
            last = {}                   # (coordinator, vid) -> last seen
            while not stop_readers.is_set():
                for v in list(acked)[rid::2][:30]:
                    with acked_lock:
                        floor = 0 if v not in acked else -1
                    r = client.execute(
                        f"FETCH PROP ON Person {v} YIELD Person.age AS a")
                    if r.error is not None or not r.data.rows:
                        continue        # structured failure: allowed
                    saw = int(r.data.rows[0][0])
                    if saw > rounds or saw < 0:
                        wrong.append(("reader", v, saw, "never written"))
                    key = (client.addr, v)
                    if saw < last.get(key, floor):
                        # a coordinator re-served an OLDER cached value
                        # for a vid it had already served newer — the
                        # stale-cache-resurrection bug
                        wrong.append(("reader", v, saw,
                                      f"regressed below {last[key]} "
                                      f"on {client.addr}"))
                    last[key] = saw
                time.sleep(0.005)
            client.close()

        writers = [threading.Thread(target=writer, args=(w,), daemon=True)
                   for w in range(3)]
        readers = [threading.Thread(target=reader, args=(i,), daemon=True)
                   for i in range(2)]
        for t in writers + readers:
            t.start()

        kill_at.wait()                  # everyone parked at round 3
        cc.kill_graphd(victim)

        for t in writers:
            t.join(120)
            assert not t.is_alive(), "writer wedged"
        stop_readers.set()
        for t in readers:
            t.join(30)
            assert not t.is_alive(), "reader wedged"

        assert not wrong, wrong[:10]
        assert recovery.get("failover_s") is not None
        assert recovery["failover_s"] < 15.0, recovery
        # every vid's final acked value is the last round a writer got
        # acked — through a coordinator crash, nothing lost
        missing = {v: a for v, a in acked.items() if a < 1}
        assert not missing, f"writes never acked: {missing}"

        # -- zero stale cross-coordinator cache hits ----------------------
        # after the storm, every SURVIVING coordinator's CACHED read
        # must converge to the final acked value within the bounded
        # propagation window; time-to-coherence is the recovery report
        t0 = time.monotonic()
        survivors = [i for i in range(3) if i != victim]
        clients = {i: _fleet_client(cc, home=i) for i in survivors}
        sample = sorted(acked)[::5]
        deadline = t0 + 10.0
        for v in sample:
            want = [[acked[v]]]
            for i, cl in clients.items():
                q = f"FETCH PROP ON Person {v} YIELD Person.age AS a"
                while True:
                    r1, r2 = cl.execute(q), cl.execute(q)   # 2nd: cached
                    if r1.error is None and r2.error is None \
                            and r1.data.rows == want \
                            and r2.data.rows == want:
                        break
                    if time.monotonic() > deadline:
                        raise AssertionError(
                            f"coordinator {i} stale for vid {v}: "
                            f"{r1.error or r1.data.rows} / "
                            f"{r2.error or r2.data.rows}, want {want}")
                    time.sleep(0.05)
        coherence_s = time.monotonic() - t0
        for cl in clients.values():
            cl.close()
        snap = stats().snapshot()
        print(f"\nfleet chaos: failover_s={recovery['failover_s']:.2f} "
              f"coherence_s={coherence_s:.2f} "
              f"failovers={snap.get('coordinator_failovers', 0):.0f} "
              f"session_moves={snap.get('session_moves', 0):.0f} "
              f"epoch_lag_p95_ms="
              f"{snap.get('epoch_propagation_lag_ms.p95', 0):.1f}")
        assert coherence_s < 10.0
    finally:
        _pop_flags()
        cc.stop()


def test_graceful_drain_under_load_sheds_nothing():
    """Planned-restart half of the same proof: DRAIN (not kill) a
    coordinator mid-storm — every statement still acks (drain refusals
    precede execution and retry transparently), zero errors of any
    kind surface to the workload."""
    cc = ChaosCluster(n_meta=1, n_storage=3, n_graph=3, parts=4,
                      replica_factor=3)
    try:
        victim = 2
        client = _fleet_client(cc, home=victim)
        results = []

        def writer():
            for k in range(120):
                results.append(client.execute(
                    f'INSERT VERTEX Person(name, age) VALUES '
                    f'{4000 + k}:("d{k}",{k % 90})'))

        t = threading.Thread(target=writer, daemon=True)
        t.start()
        while len(results) < 20:
            time.sleep(0.005)
        cc.cluster.drain_graphd(victim)
        cc.dead_graphds.add(victim)
        t.join(60)
        assert not t.is_alive()
        errs = [r.error for r in results if r.error is not None]
        assert not errs, errs[:5]
        assert client.addr != cc.cluster.graph_addrs[victim]
        for k in range(120):
            r = cc.ok(f"FETCH PROP ON Person {4000 + k} "
                      f"YIELD Person.age AS a")
            assert r.data.rows == [[k % 90]]
        client.close()
    finally:
        cc.stop()
