"""Multi-chip sharded-execution A/B bench (ISSUE 17 tentpole proof).

Measures what the mesh-native sharded plane actually buys:

  * **HBM scale-out** — with the per-DEVICE budget flag set to 1/4 of
    the snapshot, the single-chip pin REFUSES (the graph does not fit
    one chip) while the N-shard pin accepts (each shard parks ~1/N of
    the bytes); the per-shard ledger gauges are reported and must sum
    to the pinned total.
  * **Parity** — GO-3-step rows from the sharded runtime are
    byte-identical to the numpy CSR oracle (host_csr_traverse) AND to
    the single-chip runtime (the 1-vs-N A/B is an apples comparison).
  * **Goodput + exchange** — edges/s for 1-shard vs N-shard on the
    same snapshot, per-shard HBM bytes, and the bit-packed frontier
    all_to_all payload per hop (TraverseStats.exchange_bytes).

The measurement runs IN THIS PROCESS on `jax.devices()`: a chip
belongs to one process, so there is no probe child and no second arm.
It fails when the platform is not `tpu` unless the caller set
JAX_PLATFORMS=cpu (then `XLA_FLAGS=
--xla_force_host_platform_device_count=8` gives the rehearsal mesh),
and it needs at least two devices.

CLI:
  python -m nebula_tpu.tools.multichip_bench [--persons N] [--repeats R]
"""
from __future__ import annotations

import json
import os
import statistics
import sys
import time


def _run_measurement(persons: int, degree: int, steps: int,
                     repeats: int) -> dict:
    import numpy as np

    from ..bench.datagen import (SnapshotStore, host_csr_traverse,
                                 make_social_arrays, snapshot_from_arrays)
    from ..tpu import TpuRuntime, make_mesh
    from ..tpu.device import TpuUnavailable
    from ..utils.config import get_config
    from ..utils.stats import stats

    from ..tpu.device import require_tpu
    ident = require_tpu("multichip_bench")
    N = min(8, ident["count"])
    out: dict = {"device": ident, "shards": N, "persons": persons,
                 "degree": degree, "steps": steps}
    if N < 2:
        raise RuntimeError(
            f"multichip_bench needs >= 2 devices for a sharded arm, "
            f"jax.devices() has {ident['count']}")

    arrs = make_social_arrays(persons, degree, seed=7)
    snap = snapshot_from_arrays(arrs, parts=N, space="mc")
    sstore = SnapshotStore(snap)
    rt1 = TpuRuntime(make_mesh(1))
    rtN = TpuRuntime(make_mesh(N))
    snap_bytes = rtN.pin_prebuilt(snap).hbm_bytes()
    rtN.unpin("mc")
    out["snapshot_bytes"] = snap_bytes

    # ---- HBM scale-out proof: budget = snapshot/4 per device ----------
    limit = max(snap_bytes // 4, 1)
    get_config().set_dynamic("tpu_hbm_limit_bytes", limit)
    try:
        proof: dict = {"per_device_limit_bytes": limit,
                       "graph_over_budget_x": round(snap_bytes / limit, 2)}
        try:
            rt1.pin_prebuilt(snap)
            proof["single_chip_refused"] = False    # should NOT happen
        except TpuUnavailable as ex:
            proof["single_chip_refused"] = True
            proof["refusal"] = str(ex)[:200]
        dev = rtN.pin_prebuilt(snap)                # must fit: bytes/N
        shard_bytes = dev.shard_hbm_bytes()
        proof["sharded_pin_ok"] = True
        proof["shard_hbm_bytes"] = {str(k): int(v)
                                    for k, v in shard_bytes.items()}
        proof["shard_sum_matches_total"] = \
            sum(shard_bytes.values()) == dev.hbm_bytes()
        out["hbm_scaleout"] = proof
    finally:
        get_config().set_dynamic("tpu_hbm_limit_bytes", 0)

    # ---- parity + goodput A/B ----------------------------------------
    seeds = np.unique(arrs["src"][:64])[:16].tolist()
    rt1.pin_prebuilt(snap)

    def one_arm(rt, label):
        rows, st = rt.traverse(sstore, "mc", seeds, ["KNOWS"], "out",
                               steps)                  # warm + escalate
        lat = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            rows, st = rt.traverse(sstore, "mc", seeds, ["KNOWS"],
                                   "out", steps)
            lat.append(time.perf_counter() - t0)
        edges = st.edges_traversed()
        xhops = max(steps - 1, 0)
        arm = {"shards": st.shards,
               "edges_traversed": edges,
               "median_s": round(statistics.median(lat), 4),
               "edges_per_s": int(edges / statistics.median(lat)),
               "exchange_bytes": st.exchange_bytes,
               "exchange_bytes_per_hop":
                   st.exchange_bytes // xhops if xhops else 0,
               "device_s": round(st.device_s, 4)}
        key = sorted((int(e.src), e.name, int(e.ranking), int(e.dst))
                     for _, e, _ in rows)
        return arm, key

    armN, keyN = one_arm(rtN, "sharded")
    arm1, key1 = one_arm(rt1, "single")
    out["single_chip"] = arm1
    out["sharded"] = armN
    out["rows_identical_1_vs_N"] = key1 == keyN

    # numpy oracle: same CSR arrays, vectorized host expansion
    total, kept, dst, w = host_csr_traverse(snap, seeds, steps,
                                            materialize=True)
    devd = np.asarray(sorted(k[3] for k in keyN), np.int64)
    out["rows_identical_vs_numpy"] = (
        kept == len(keyN) and
        bool((np.sort(dst.astype(np.int64)) == devd).all()))
    out["numpy_edges_traversed"] = total

    # the mesh gauges the run left behind
    snapm = stats().snapshot()
    out["tpu_shards_gauge"] = snapm.get("tpu_shards")
    out["tpu_all_to_all_bytes"] = snapm.get("tpu_all_to_all_bytes", 0)
    return out


def multichip_sweep(persons: int = 120_000, degree: int = 6,
                    steps: int = 3, repeats: int = 5) -> dict:
    """The bench.py `multichip` block: the 1-vs-N-shard A/B on the
    devices this process holds.  Raises when a parity or HBM proof
    fails — a failed phase must fail the run."""
    res = _run_measurement(persons, degree, steps, repeats)
    proof = res["hbm_scaleout"]
    failed = [k for k, ok in (
        ("single_chip_refused", proof["single_chip_refused"]),
        ("shard_sum_matches_total", proof["shard_sum_matches_total"]),
        ("rows_identical_1_vs_N", res["rows_identical_1_vs_N"]),
        ("rows_identical_vs_numpy", res["rows_identical_vs_numpy"]),
    ) if not ok]
    if failed:
        raise AssertionError(f"multichip proofs failed: {failed}: "
                             f"{json.dumps(res)[:2000]}")
    res["speedup_Nshard_vs_1"] = round(
        res["sharded"]["edges_per_s"]
        / max(res["single_chip"]["edges_per_s"], 1), 3)
    return res


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        description="1-vs-N-shard mesh execution A/B")
    ap.add_argument("--persons", type=int,
                    default=int(os.environ.get(
                        "NEBULA_BENCH_MULTICHIP_PERSONS", 120_000)))
    ap.add_argument("--degree", type=int, default=6)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--repeats", type=int, default=5)
    args = ap.parse_args(argv)
    res = multichip_sweep(args.persons, args.degree, args.steps,
                          args.repeats)
    print(json.dumps(res, indent=2))
    return 0


if __name__ == "__main__":
    sys.exit(main())
