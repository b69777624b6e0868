"""Per-iteration jitted step kernels for the vertex-program engine.

Each algorithm's edge-propagate/combine/apply step is ONE jitted
kernel (the tentpole contract): gather source state along the flat
edge list, combine per edge, segment-reduce by destination (scatter
add/min — the segment-sum shape of PAPERS.md), apply the vertex
update, and report the convergence scalars.  The host drives the
iteration loop (algo/engine.py) so termination, progress reporting
and kill checks land BETWEEN dispatches.

State arrays are float64/int64 (x64 is enabled package-wide, see
tpu/__init__.py) so host-oracle parity is exact for the integer
algorithms and tight (documented 1e-9 relative tolerance) for
PageRank.

Kernels are cached per (algorithm, shape signature) — the jit trace
is reused across iterations and runs.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

BIG = np.iinfo(np.int64).max

_cache: Dict[Tuple, object] = {}

#: bound on retained executables: n_slots changes whenever a growing
#: snapshot re-pins with a larger vmax and damping/tol are
#: per-statement parameters, so an unbounded cache would accumulate
#: XLA executables for the process lifetime (the same hazard
#: TpuRuntime._seed_fns caps)
_CACHE_CAP = 32


def _cached(key, build):
    fn = _cache.pop(key, None)      # re-insert on hit: recency order
    if fn is None:
        fn = build()
    _cache[key] = fn
    while len(_cache) > _CACHE_CAP:
        _cache.pop(next(iter(_cache)))
    return fn


def pagerank_step(n_slots: int, damping: float, tol: float):
    """(rank, esrc_s, edst_s, out_inv_e, dangling_mask, vmask, n) →
    (rank', l1_delta, active) — active counts vertices whose rank
    moved more than tol this iteration (the live-progress number).

    Edges arrive DST-SORTED (AlgoGraph.by_dst) and the per-vertex
    combine is a sorted segment sum (scatter-add by destination,
    `indices_are_sorted`) — the same shape as the WCC/SSSP
    min-combines.  It replaced a float64 `jnp.cumsum` prefix-sum
    reduction: x64 is on package-wide and the TPU emulates f64, and
    the compiler needed 194 s for that cumsum at 600,000 edges and did
    not finish in 10 minutes at 30,000,000 (a log-depth
    `lax.associative_scan` did no better), while this step compiles
    in about a second at 1,000,000 slots / 30,000,000 edges
    (tests/unit/test_tpu_compile.py keeps that so).  Updates to one
    slot are applied in edge order, so the same graph gives
    bit-identical ranks run-to-run; vs the oracle's np.add.at order
    the result stays within the documented 1e-8 tolerance."""
    def build():
        def step(rank, esrc_s, edst_s, out_inv_e, dmask, vmask, n):
            contrib = rank[esrc_s] * out_inv_e
            acc = jax.ops.segment_sum(contrib, edst_s,
                                      num_segments=n_slots,
                                      indices_are_sorted=True)
            base = (1.0 - damping
                    + damping * jnp.sum(jnp.where(dmask, rank, 0.0))) / n
            new = jnp.where(vmask, base + damping * acc, 0.0)
            moved = jnp.abs(new - rank)
            return new, jnp.sum(moved), \
                jnp.sum(moved > tol, dtype=jnp.int64)
        return jax.jit(step)
    return _cached(("pagerank", n_slots, damping, tol), build)


def wcc_step(n_slots: int):
    """(label, active, esrc, edst) → (label', active', changed) —
    min-label hooking: every active vertex pushes its label to its
    neighbors; a vertex whose label drops joins the next frontier."""
    def build():
        def step(label, active, esrc, edst):
            send = jnp.where(active[esrc], label[esrc], BIG)
            cand = jnp.full((n_slots,), BIG, label.dtype).at[edst].min(
                send, indices_are_sorted=True)
            new = jnp.minimum(label, cand)
            changed = new < label
            return new, changed, jnp.sum(changed, dtype=jnp.int64)
        return jax.jit(step)
    return _cached(("wcc", n_slots), build)


def sssp_step(n_slots: int, weighted: bool):
    """(dist, frontier, esrc, edst[, w]) → (dist', frontier', changed)
    — weighted frontier relaxation (Bellman-Ford over the active
    set): frontier vertices push dist+w along their edges, scatter-min
    by destination, and strictly-improved vertices form the next
    frontier (strict `<` guarantees termination even with 0-weight
    cycles)."""
    def build():
        if weighted:
            def step(dist, frontier, esrc, edst, w):
                send = jnp.where(frontier[esrc], dist[esrc] + w,
                                 jnp.inf)
                cand = jnp.full((n_slots,), jnp.inf,
                                dist.dtype).at[edst].min(
                    send, indices_are_sorted=True)
                new = jnp.minimum(dist, cand)
                changed = new < dist
                return new, changed, jnp.sum(changed, dtype=jnp.int64)
        else:
            def step(dist, frontier, esrc, edst):
                send = jnp.where(frontier[esrc], dist[esrc] + 1.0,
                                 jnp.inf)
                cand = jnp.full((n_slots,), jnp.inf,
                                dist.dtype).at[edst].min(
                    send, indices_are_sorted=True)
                new = jnp.minimum(dist, cand)
                changed = new < dist
                return new, changed, jnp.sum(changed, dtype=jnp.int64)
        return jax.jit(step)
    return _cached(("sssp", n_slots, weighted), build)
