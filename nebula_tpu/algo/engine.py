"""`CALL algo.*` execution driver (ISSUE 13 tentpole).

One shared iterative vertex-program loop drives all three algorithms:
dense per-vertex state arrays + an edge-propagate/combine/apply step
compiled as ONE jitted kernel per iteration (algo/kernels.py), with
convergence/max-iteration termination decided on the HOST between
dispatches.  That host-side seam is the whole point for a production
engine: between iterations the statement

  * stamps live progress into its LiveQuery row — SHOW QUERIES shows
    `algo.pagerank[iter k/K active_frontier=N]` while it runs;
  * runs the PR 5 cancel check — KILL QUERY and query_timeout land
    BETWEEN iterations with partial state discarded;
  * hits the `algo:iter` failpoint (deterministic delay/raise for the
    kill/stall tests);
  * emits `algo_iterations` / `algo_iter_us` and a `tpu:algo_iter`
    trace span per device dispatch.

Around the loop a statement's other seconds have a span and a series
each: `algo:prepare` / `algo_prepare_s` (the flat edge list
and its destination sort, when they are built), `algo:put` /
`algo_put_s` (the edge arrays' upload, once a graph, and every run's
own arrays), `algo:assemble` / `algo_assemble_s` (`assemble_rows`);
`algo_edge_visits{algo}` counts rows x iterations run, and the gauge
`tpu_algo_bytes_resident` says what `_dev_cache` holds on the device.
A device run that arrives with no trace active is rooted as
`query:tpu.algo` (`tpu/runtime.py` `statement_root`), so its spans fold
into the phase ledger like any other device statement's; and it keeps a
`TraverseStats` as they do (its gate waits, kernel seconds, puts, the
fetch of its final state, row assembly: `info["stats"]`), which the
runtime settles into the process-wide device series once a statement
(`TpuRuntime.algo_account`).

Execution modes (the `mode` parameter): `auto` uses the device plane
when a TpuRuntime serves the space and falls back to the numpy host
oracles otherwise (`algo_fallback` counts why); `device` errors
instead of falling back; `host` forces the oracle (the bench A/B
lever).  Both paths share graph preparation (algo/graph.py) and row
assembly, so rows are identical by construction up to PageRank's
documented float tolerance.

The distributed store is not yet served: algorithms need the dense
CSR snapshot (graphd-resident or device-pinned); ROADMAP item 1's
sharded mesh is where the partitioned variant lands.
"""
from __future__ import annotations

import time
from contextlib import nullcontext
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..utils import trace
from ..utils.stats import stats
from . import ALGORITHMS, DEFAULT_MAX_ITER, REQUIRED, _DIRECTIONS, _MODES
from .graph import AlgoGraph, blocks_for, build_algo_graph
from .oracles import BIG, pagerank_np, sssp_np, wcc_np


class AlgoError(Exception):
    """User-facing algo-plane error (the executor re-raises as
    ExecError so the client sees ExecutionError: ...)."""


# -- graph preparation (shared by both modes) -------------------------------

#: host-snapshot LRU for stores WITHOUT a device runtime (a runtime's
#: pin() already caches per epoch); key (space, store uid) → (epoch, snap)
_snap_cache: Dict[Tuple, Tuple[int, Any]] = {}
#: flat-edge LRU; key (id(snap), blocks, weight) → (snap ref, AlgoGraph)
_graph_cache: Dict[Tuple, Tuple[Any, AlgoGraph]] = {}
#: device-resident edge arrays; same key → (snap ref, dict of jax
#: arrays).  BOTH id(snap)-keyed caches hold the snapshot itself: a
#: key is only reachable while its snapshot is alive, so a recycled
#: object id can never serve another graph's arrays.
_dev_cache: Dict[Tuple, Tuple[Any, Dict[str, Any]]] = {}


def _lru_put(cache: Dict, key, value, cap: int = 4):
    cache[key] = value
    while len(cache) > cap:
        cache.pop(next(iter(cache)))


def _lru_get(cache: Dict, key):
    """Dict-as-LRU read: re-insert on hit so eviction tracks RECENCY,
    not insertion order (a hot entry must survive a cold parade)."""
    ent = cache.pop(key, None)
    if ent is not None:
        cache[key] = ent
    return ent


def _host_snapshot(qctx, space: str):
    """-> (CsrSnapshot, space-data) for the statement's space, or
    raise AlgoError when the store has no dense-snapshot form."""
    store = qctx.store
    snap = getattr(store, "snap", None)
    if snap is not None:                 # prebuilt bench SnapshotStore
        return snap, store.space(space)
    try:
        sd = store.space(space)
        sd.dense_id
    except AttributeError:
        raise AlgoError(
            "CALL algo.* needs the dense-snapshot store (standalone "
            "engine or device-pinned space); the distributed store "
            "is not yet served") from None
    rt = getattr(qctx, "tpu_runtime", None)
    if rt is not None:
        dev = rt.pin(store, space)
        hd = dev.delta.host if dev.delta is not None else None
        if hd is not None and (hd.total_edges() or hd.total_tombs()):
            # algorithms read the BASE host CSR directly — pending delta
            # edges live only in the mirror, so fold them in with a full
            # re-pin before handing the adjacency out (ISSUE 19)
            dev = rt.pin(store, space, force=True)
        return dev.host, sd
    key = (space, getattr(sd, "uid", None) or id(sd))
    ent = _lru_get(_snap_cache, key)
    if ent is not None and ent[0] == sd.epoch:
        return ent[1], sd
    from ..graphstore.csr import build_snapshot
    snap = build_snapshot(store, space)
    _lru_put(_snap_cache, key, (sd.epoch, snap))
    return snap, sd


def _algo_graph(snap, block_keys, weight_prop) -> AlgoGraph:
    key = (id(snap), tuple(block_keys), weight_prop)
    ent = _lru_get(_graph_cache, key)
    if ent is not None:
        return ent[1]
    t0 = time.perf_counter()
    with trace.span("algo:prepare"):
        g = build_algo_graph(snap, block_keys, weight_prop)
    stats().add_value("algo_prepare_s", time.perf_counter() - t0)
    _lru_put(_graph_cache, key, (snap, g))
    return g


def _by_dst(g: AlgoGraph):
    """`g.by_dst()`; the sort, where this call makes it, is part of
    the graph's preparation."""
    if g.sorted_by_dst():
        return g.by_dst()
    t0 = time.perf_counter()
    with trace.span("algo:prepare"):
        view = g.by_dst()
    stats().add_value("algo_prepare_s", time.perf_counter() - t0)
    return view


def _put(dev0, *arrays):
    """The arrays on the device, waited for: `algo:put` then holds the
    transfer and not just its enqueue (the first iteration would wait
    for it otherwise)."""
    import jax
    return jax.block_until_ready([jax.device_put(a, dev0) for a in arrays])


def _put_done(st, t0: float):
    """One `algo:put` span closed: its seconds in the series and in the
    statement's own stats."""
    dt = time.perf_counter() - t0
    stats().add_value("algo_put_s", dt)
    st.put_s += dt


def _fetch_state(arr, st, g: AlgoGraph) -> np.ndarray:
    """The final state array brought to the host, with the fetch's
    seconds and bytes in the statement's stats: the entries of real
    vertices are what row assembly keeps."""
    t0 = time.perf_counter()
    with trace.span("device:fetch"):
        out = np.asarray(arr)
    st.fetch_s += time.perf_counter() - t0
    st.fetch_bytes += int(out.nbytes)
    st.fetch_bytes_kept += g.n_vertices * out.itemsize
    return out


def _device_edges(rt, snap, block_keys, weight_prop,
                  g: AlgoGraph, st) -> Dict[str, Any]:
    """Device-resident flat edge arrays, uploaded once per (snapshot,
    block set, weight) and reused by every iteration and every run.

    Edges go up DST-SORTED (AlgoGraph.by_dst): PageRank's segment sum
    and the min-combines pass indices_are_sorted — min is exactly
    order-independent, so the sort can never change WCC/SSSP
    results."""
    key = (id(snap), tuple(block_keys), weight_prop)
    ent = _lru_get(_dev_cache, key)
    if ent is not None:
        return ent[1]
    order, esrc_s, edst_s = _by_dst(g)
    dev0 = rt.mesh.devices.reshape(-1)[0]
    t0 = time.perf_counter()
    with trace.span("algo:put"):
        host = {"esrc": esrc_s.astype(np.int32),
                "edst": edst_s.astype(np.int32), "vmask": g.vmask}
        if g.weight is not None:
            host["weight"] = g.weight[order]
        arrs = dict(zip(host, _put(dev0, *host.values())))
    _put_done(st, t0)
    _lru_put(_dev_cache, key, (snap, arrs))
    stats().gauge("tpu_algo_bytes_resident",
                  sum(int(a.nbytes) for _snap, held in _dev_cache.values()
                      for a in held.values()))
    return arrs


# -- parameter resolution ---------------------------------------------------


def resolve_params(func: str, given: Dict[str, Any]) -> Dict[str, Any]:
    """Defaults + type/range checks on the literal parameter values
    (the validator already vetted names/required/yields)."""
    spec = ALGORITHMS[func]
    p = {k: v for k, v in spec.params.items() if v is not REQUIRED}
    p.update(given)
    if p.get("mode") not in _MODES:
        raise AlgoError(f"mode must be one of {_MODES}")
    if "direction" in p and p["direction"] not in _DIRECTIONS:
        raise AlgoError(f"direction must be one of {_DIRECTIONS}")
    mi = p.get("max_iter")
    if not isinstance(mi, int) or isinstance(mi, bool) or mi < 0:
        raise AlgoError("max_iter must be a non-negative integer")
    if func == "pagerank":
        d = p["damping"]
        if not isinstance(d, (int, float)) or not 0.0 < float(d) < 1.0:
            raise AlgoError("damping must be in (0, 1)")
        t = p["tol"]
        if not isinstance(t, (int, float)) or float(t) < 0:
            raise AlgoError("tol must be non-negative")
    if func == "sssp":
        w = p.get("weight")
        if w is not None and not isinstance(w, str):
            raise AlgoError("weight must name an edge prop (string)")
    et = p.get("edge_types")
    if isinstance(et, str):
        p["edge_types"] = [et]
    elif et is not None and not (isinstance(et, list)
                                 and all(isinstance(x, str) for x in et)):
        raise AlgoError("edge_types must be a string or list of strings")
    return p


def _effective_max_iter(func: str, params: Dict[str, Any],
                        g: AlgoGraph) -> int:
    k = int(params.get("max_iter") or 0)
    if k > 0:
        return k
    dflt = DEFAULT_MAX_ITER[func]
    if func in ("wcc", "sssp"):
        # both converge within the graph diameter; n_vertices bounds it
        return max(min(dflt, max(g.n_vertices, 1)), 1)
    return dflt


# -- the shared iteration loop ----------------------------------------------


def _iterate(name: str, max_iter: int, live, body,
             iter_us: Optional[List[int]] = None, n_edges: int = 0) -> int:
    """Drive `body(it) -> (active, converged)` with the per-iteration
    contract: cancel check (kill/deadline land HERE, between
    iterations), the `algo:iter` failpoint, the `tpu:algo_iter` span,
    `algo_*` metrics, and the live-progress stamp SHOW QUERIES
    renders.  Returns the iterations actually run; `iter_us` (when
    given) collects per-iteration wall µs — the bench's A/B probe.
    Every iteration visits each of the graph's `n_edges` rows:
    `algo_edge_visits{algo}` counts them."""
    from ..utils import cancel as _cancel
    from ..utils.failpoints import fail
    iters = 0
    for it in range(1, max_iter + 1):
        _cancel.check()
        fail.hit("algo:iter", key=name)
        t0 = time.perf_counter()
        with trace.span("tpu:algo_iter", algo=name, iteration=it):
            active, converged = body(it)
        us = int((time.perf_counter() - t0) * 1e6)
        stats().inc_labeled("algo_iterations", {"algo": name})
        stats().inc_labeled("algo_edge_visits", {"algo": name}, n_edges)
        stats().observe("algo_iter_us", us, {"algo": name})
        if iter_us is not None:
            iter_us.append(us)
        if live is not None:
            live.set_operator(f"algo.{name}[iter {it}/{max_iter} "
                              f"active_frontier={int(active)}]")
        iters = it
        if converged:
            break
    # a kill/deadline that landed during the LAST body must still win
    _cancel.check()
    return iters


# -- device drivers ---------------------------------------------------------


def _device_pagerank(rt, snap, block_keys, g, params, live, st,
                     iter_us=None):
    from . import kernels
    dev = _device_edges(rt, snap, block_keys, None, g, st)
    damping, tol = float(params["damping"]), float(params["tol"])
    step = kernels.pagerank_step(g.n_slots, damping, tol)
    n = float(max(g.n_vertices, 1))
    dev0 = rt.mesh.devices.reshape(-1)[0]
    t0 = time.perf_counter()
    with trace.span("algo:put"):
        outdeg = g.out_degree()
        out_inv = np.zeros(g.n_slots)
        nz = outdeg > 0
        out_inv[nz] = 1.0 / outdeg[nz]
        _order, esrc_s, _edst_s = g.by_dst()
        # per-edge 1/outdeg pre-gathered once (static within a run): the
        # iteration kernel then needs ONE gather per edge, not two
        out_inv_e, dmask_d, rank0 = _put(
            dev0, out_inv[esrc_s], g.vmask & ~nz,
            np.where(g.vmask, 1.0 / n, 0.0))
    _put_done(st, t0)
    state = {"rank": rank0}
    K = _effective_max_iter("pagerank", params, g)

    def body(it):
        (rank, delta, active), _us = rt.algo_dispatch(
            "algo.pagerank", step, state["rank"], dev["esrc"],
            dev["edst"], out_inv_e, dmask_d, dev["vmask"], n, stats=st)
        state["rank"] = rank
        return int(active), float(delta) < tol

    iters = _iterate("pagerank", K, live, body, iter_us, g.n_edges)
    return _fetch_state(state["rank"], st, g), iters


def _device_wcc(rt, snap, block_keys, g, params, live, st,
                iter_us=None):
    from . import kernels
    dev = _device_edges(rt, snap, block_keys, None, g, st)
    step = kernels.wcc_step(g.n_slots)
    dev0 = rt.mesh.devices.reshape(-1)[0]
    t0 = time.perf_counter()
    with trace.span("algo:put"):
        (label0,) = _put(dev0, np.where(
            g.vmask, np.arange(g.n_slots, dtype=np.int64), BIG))
    _put_done(st, t0)
    state = {"label": label0, "active": dev["vmask"]}
    K = _effective_max_iter("wcc", params, g)

    def body(it):
        (label, active, changed), _us = rt.algo_dispatch(
            "algo.wcc", step, state["label"], state["active"],
            dev["esrc"], dev["edst"], stats=st)
        state["label"], state["active"] = label, active
        return int(changed), int(changed) == 0

    iters = _iterate("wcc", K, live, body, iter_us, g.n_edges)
    return _fetch_state(state["label"], st, g), iters


def _device_sssp(rt, snap, block_keys, g, params, live, st, src_dense,
                 iter_us=None):
    from . import kernels
    weight_prop = params.get("weight")
    dev = _device_edges(rt, snap, block_keys, weight_prop, g, st)
    step = kernels.sssp_step(g.n_slots, weight_prop is not None)
    dev0 = rt.mesh.devices.reshape(-1)[0]
    t0 = time.perf_counter()
    with trace.span("algo:put"):
        dist0 = np.full(g.n_slots, np.inf)
        dist0[src_dense] = 0.0
        front0 = np.zeros(g.n_slots, bool)
        front0[src_dense] = True
        dist_d, front_d = _put(dev0, dist0, front0)
    _put_done(st, t0)
    state = {"dist": dist_d, "front": front_d}
    K = _effective_max_iter("sssp", params, g)
    extra = (dev["weight"],) if weight_prop is not None else ()

    def body(it):
        (dist, front, changed), _us = rt.algo_dispatch(
            "algo.sssp", step, state["dist"], state["front"],
            dev["esrc"], dev["edst"], *extra, stats=st)
        state["dist"], state["front"] = dist, front
        return int(changed), int(changed) == 0

    iters = _iterate("sssp", K, live, body, iter_us, g.n_edges)
    return _fetch_state(state["dist"], st, g), iters


# -- row assembly (one code path for device AND host rows) ------------------


def assemble_rows(func: str, g: AlgoGraph,
                  state: np.ndarray) -> List[List[Any]]:
    """Final state array → full-width rows, ordered by vid (the
    documented deterministic order — identical for device and host
    because both sort the same vid domain the same way)."""
    d2v = g.dense_to_vid
    out: List[List[Any]] = []
    live = np.flatnonzero(g.vmask).tolist()
    if func == "pagerank":
        for d in live:
            out.append([d2v[d], float(state[d])])
    elif func == "wcc":
        for d in live:
            out.append([d2v[d], d2v[int(state[d])]])
    else:  # sssp: reached vertices only
        for d in live:
            v = float(state[d])
            if np.isfinite(v):
                out.append([d2v[d], v])
    try:
        out.sort(key=lambda r: r[0])
    except TypeError:        # heterogeneous vids: canonical repr order
        out.sort(key=lambda r: repr(r[0]))
    return out


# -- the shared driver (executor AND bench entry point) ---------------------


def run_algorithm(func: str, params: Dict[str, Any], snap, sd,
                  rt=None, live=None, iter_us: Optional[List[int]] = None,
                  on_fallback=None):
    """Run one algorithm against a host CsrSnapshot, device when `rt`
    serves it (per `params['mode']`), numpy oracle otherwise.

    -> (rows, info) where rows are full-width [vid, value] rows in the
    canonical vid order and info = {'mode', 'iterations', 'n_edges',
    'n_vertices'}, and after a device run 'stats', the statement's
    `TraverseStats`.  `iter_us` collects per-iteration wall µs on the
    device path (the bench's A/B probe); `on_fallback(cause)` is told
    why an auto-mode device run failed before the oracle takes over.

    With a runtime and no trace active (an embedded runtime: the
    benchmark's proxy cell, a tool) the statement is rooted here as
    `query:tpu.algo`, as the runtime's own entries are."""
    if rt is None:
        root = nullcontext()
    else:
        from ..tpu.runtime import statement_root
        root = statement_root("algo", snap.space)
    with root:
        return _run_algorithm(func, params, snap, sd, rt, live,
                              iter_us, on_fallback)


def _run_algorithm(func, params, snap, sd, rt, live, iter_us,
                   on_fallback):
    from ..utils import cancel as _cancel

    params = resolve_params(func, dict(params))
    direction = params.get("direction", "out")
    if func == "pagerank":
        direction = "out"
    elif func == "wcc":
        direction = "both"
    try:
        block_keys = blocks_for(snap, params.get("edge_types"),
                                direction)
    except KeyError as ex:
        raise AlgoError(str(ex)) from None
    weight_prop = params.get("weight") if func == "sssp" else None
    try:
        g = _algo_graph(snap, block_keys, weight_prop)
    except (KeyError, ValueError) as ex:
        raise AlgoError(str(ex)) from None
    if g.weight is not None and g.n_edges and g.weight.min() < 0:
        raise AlgoError(
            f"algo.sssp requires non-negative weights "
            f"(prop `{weight_prop}' has negative values)")

    src_dense = None
    if func == "sssp":
        try:
            src_dense = sd.dense_id(params["src"])
        except Exception:  # noqa: BLE001 — vid-type mismatch: unknown
            src_dense = -1
        if src_dense is None or src_dense < 0 \
                or not g.vmask[src_dense]:
            # unknown source: no reachable set — empty result, not an
            # error (FIND PATH's missing-vid contract)
            return [], {"mode": "none", "iterations": 0,
                        "n_edges": g.n_edges,
                        "n_vertices": g.n_vertices}

    mode = params["mode"]
    if mode == "device" and rt is None:
        raise AlgoError("mode=device but no device runtime serves "
                        "this engine")

    state, iters, ran_mode, st = None, 0, "host", None
    t_start = time.perf_counter()
    if mode != "host" and rt is not None:
        from ..tpu.assemble import TraverseStats
        from ..tpu.device import TpuUnavailable, note_host_fallback
        from ..tpu.traverse import _JAX_RT_ERRORS
        st = TraverseStats()
        try:
            if func == "pagerank":
                state, iters = _device_pagerank(
                    rt, snap, block_keys, g, params, live, st, iter_us)
            elif func == "wcc":
                state, iters = _device_wcc(
                    rt, snap, block_keys, g, params, live, st, iter_us)
            else:
                state, iters = _device_sssp(
                    rt, snap, block_keys, g, params, live, st, src_dense,
                    iter_us)
            ran_mode = "device"
        except (TpuUnavailable,) + _JAX_RT_ERRORS as ex:
            if mode == "device":
                raise AlgoError(f"device execution failed: {ex}") \
                    from ex
            stats().inc_labeled(
                "algo_fallback",
                {"algo": func, "reason": type(ex).__name__})
            cause = note_host_fallback(f"algo.{func}", ex)
            if on_fallback is not None:
                on_fallback(cause)
            state = st = None

    if state is None:                   # host oracle (mode or fallback)
        _cancel.check()
        if live is not None:
            live.set_operator(f"algo.{func}[host oracle]")
        if func == "pagerank":
            state, iters = pagerank_np(
                g, float(params["damping"]),
                _effective_max_iter(func, params, g),
                float(params["tol"]), check=_cancel.check)
        elif func == "wcc":
            state, iters = wcc_np(g), 1
        else:
            state, iters = sssp_np(g, src_dense), 1
        _cancel.check()

    stats().inc_labeled("algo_runs", {"algo": func, "mode": ran_mode})
    t0 = time.perf_counter()
    with trace.span("algo:assemble"):
        rows = assemble_rows(func, g, state)
    stats().add_value("algo_assemble_s", time.perf_counter() - t0)
    info = {"mode": ran_mode, "iterations": iters,
            "n_edges": g.n_edges, "n_vertices": g.n_vertices}
    if st is not None:
        st.steps, st.result_edges = iters, len(rows)
        st.mat_s = time.perf_counter() - t0
        st.total_s = time.perf_counter() - t_start
        rt.algo_account(st)
        info["stats"] = st
    return rows, info


# -- the executor entry point -----------------------------------------------


def run_call_algo(node, qctx, ectx):
    """Executor body for the CallAlgo plan node."""
    from ..core.value import DataSet
    from ..utils.workload import current_live

    a = node.args
    func = a["algo"]
    snap, sd = _host_snapshot(qctx, a["space"])

    def note_fallback(cause):
        qctx.last_tpu_fallback = cause

    rows, _info = run_algorithm(
        func, a["params"], snap, sd,
        rt=getattr(qctx, "tpu_runtime", None),
        live=current_live(), on_fallback=note_fallback)
    cols = a["yield"]                   # [(col, alias), ...]
    spec = ALGORITHMS[func]
    idx = {c: i for i, c in enumerate(spec.yield_cols)}
    names = [al for _, al in cols]
    sel = [idx[c] for c, _ in cols]
    if sel == list(range(len(spec.yield_cols))):
        return DataSet(names, rows)
    return DataSet(names, [[r[i] for i in sel] for r in rows])
