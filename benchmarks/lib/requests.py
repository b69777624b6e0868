"""The one general traffic generator: a mix file (templates with shares,
K, the edge type start vertices are drawn over) + the reference graph +
the seed -> a fixed list of K requests, each with the reference's row
count.

Start vertices are drawn uniformly from the vertices with an out-edge, as
nebula-bench samples person.csv, and then stratified: for each template
CANDIDATES x k candidates are drawn, ordered by the reference's row count
and those at the k evenly spaced quantiles (i + 0.5) / k kept — the
distribution a uniform draw samples, without the sampling noise, so that
every seed's list holds the same set of sizes in another order (PERF.md
section 4 has the readings: a plain draw of six moves the proxy cell's
rate by up to 19% from seed to seed).

The list is in the seed's order, rotated so that it ends with its heaviest
request.  It is replayed as a cycle, so the rotation only fixes where the
cycle starts; the warm-up sends the list's last request first, and the
heaviest request first means the runtime's edge budgets climb their
ladder once, straight to where they stay, in every run (PERF.md section
6: the ladder's history set the device seconds of every later statement).
"""
from __future__ import annotations

import numpy as np

from . import loader

CANDIDATES = 8      # candidates drawn per request kept


def op_module(op: str):
    return loader.module("reference/ops", op)


def make_requests(mix: dict, ref, seed: int) -> list:
    rng = np.random.default_rng([int(seed), 0x72657173])
    K = int(mix["requests"])
    tpls = mix["templates"]
    shares = np.asarray([float(t.get("share", 1)) for t in tpls])
    exact = shares / shares.sum() * K
    counts = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - counts), kind="stable")[:K - counts.sum()]:
        counts[i] += 1
    eligible = np.flatnonzero(ref.out_degree(mix["start_vertex"]["etype"]) >= 1)
    out = []
    for t, k in zip(tpls, counts.tolist()):
        if k == 0:
            continue
        op = op_module(t["op"])
        c = min(CANDIDATES * k, eligible.size)
        cand = rng.choice(eligible, size=c, replace=False)
        rows = np.asarray([int(op.count(ref, t, v)) for v in cand.tolist()])
        order = np.lexsort((cand, rows))
        at = order[((np.arange(k) + 0.5) / k * c).astype(int)]
        for v, n in zip(cand[at].tolist(), rows[at].tolist()):
            text = t["text"].replace("$v", str(v))
            # an operation may bind further placeholders (a path's target)
            for key, val in getattr(op, "params", lambda *_: {})(ref, t, v).items():
                text = text.replace(key, val)
            out.append({"template": t, "start": v, "text": text, "rows": n})
    out = [out[i] for i in rng.permutation(len(out)).tolist()]
    heaviest = max(range(len(out)), key=lambda i: (out[i]["rows"], -i))
    out = out[heaviest + 1:] + out[:heaviest + 1]
    for i, r in enumerate(out):
        r["idx"] = i
    return out
