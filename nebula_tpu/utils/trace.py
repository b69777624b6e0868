"""Distributed query tracing — trace/span ids carried in the RPC envelope.

The reference ships per-node PROFILE timings but nothing that crosses
the graphd process boundary; a slow cluster query's time disappears
into storaged.  This module is the cross-service half of the
observability layer (ISSUE 1 tentpole): a per-query trace id plus span
ids ride the JSON-TCP envelope (cluster.rpc), every service opens child
spans around its work, and the spans a REMOTE service produced while
handling an RPC are returned in the reply and grafted into the caller's
trace — so the coordinator (the graphd that ran the statement) ends up
holding ONE stitched tree covering graphd executors, storaged reads,
raft appends and the device put/dispatch/fetch phases.  Queryable via
`GET /traces` on the webservice and `SHOW TRACES` in nGQL.

Design constraints:
  * zero cost when no trace is active — `span()` is a no-op context;
  * thread-pool safe — the scheduler and the storage fan-out run on
    pools, so the context is snapshot/restore (`current_ctx` /
    `use_ctx`), and sinks are plain lists (append is atomic);
  * spans are plain dicts the moment they finish (JSON-safe: they ship
    in RPC replies and out of the /traces endpoint verbatim).

Span fields: tid, sid, psid (parent span id), name, svc (service
role), t0 (epoch seconds), dur_us, attrs (flat dict).  Remote spans
grafted from an RPC reply additionally carry remote=True.

One clock (ISSUE 24): a span's start and end are both readings of
`time.perf_counter_ns`; `t0` is that start moved to epoch seconds
through ONE per-process offset, so intervals of one process compare
exactly.  While a `jax.profiler` session is collecting (and jax is
imported in the process), the spans of every trace that opens are also
`jax.profiler.TraceAnnotation`s, so the session shows the program's
spans on the `/host:CPU` thread lines, on the device plane's clock;
outside a session a trace pays one flag test (measured: entering an
annotation on every span cost a GIL-bound cell its share of 5% more
interpreter work, PERF.md section 6, PR 24).  When a statement's root
(`query:*`) closes, the self times of
its spans are folded ONCE into `stmt_phase_us{phase}` /
`stmt_phase_n{phase}` (`fold_phases`): the closed per-statement time
budget `/metrics` and the benchmark read.  A statement's root is
opened where the statement ENTERS: by graphd (`query:<kind>`,
`exec/engine.py` `statement_trace`) or, for a device statement that
arrives with no trace active (an embedded `TpuRuntime`: `pin_prebuilt`,
the tools, the benchmark's proxy cells), by the runtime's own entry
(`query:tpu.<entry>`, `tpu/runtime.py` `statement_root`); both obey
`enable_query_tracing`, and a statement never has two.
"""
from __future__ import annotations

import itertools
import os
import sys
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, List, Optional, Tuple

from .stats import stats

_tls = threading.local()
_span_seq = itertools.count(1)
# span ids must not collide across processes (a trace stitches spans
# from graphd + storaged + metad); prefix with a per-process token
_PROC = f"{os.getpid():x}"


# one monotonic clock for starts and lengths; epoch seconds only in
# the JSON, through this one offset
_now_ns = time.perf_counter_ns
_EPOCH_OFFSET_NS = time.time_ns() - time.perf_counter_ns()


def _new_id(kind: str) -> str:
    return f"{kind}{_PROC}-{next(_span_seq)}"


def _epoch_s(ns: int) -> float:
    """A perf_counter_ns reading as epoch seconds, on whole
    microseconds (so `round(t0 * 1e6)` gives the start back exactly)."""
    return ((_EPOCH_OFFSET_NS + ns) // 1000) / 1e6


_annotation_cls = None


def _profiling() -> bool:
    """True while a jax profiler session is collecting: spans opened
    under a trace that starts now become `TraceAnnotation`s.  jax is
    looked up in sys.modules, never imported from here; outside a
    session this is one flag test per trace."""
    global _annotation_cls
    cls = _annotation_cls
    if cls is None:
        prof = sys.modules.get("jax.profiler")
        if prof is None:
            return False
        cls = _annotation_cls = prof.TraceAnnotation
    return cls.is_enabled()


def _annotate(name: str):
    ann = _annotation_cls(name)
    ann.__enter__()
    return ann


class _Ctx:
    __slots__ = ("tid", "sid", "sink", "service", "annotate")

    def __init__(self, tid: str, sid: str, sink: List[dict], service: str,
                 annotate: bool = False):
        self.tid = tid
        self.sid = sid
        self.sink = sink
        self.service = service
        # spans of this trace are profiler annotations too: decided
        # once, where the trace (or a remote handler's part of it) opens
        self.annotate = annotate


def _get_ctx() -> Optional[_Ctx]:
    return getattr(_tls, "ctx", None)


def current_ctx() -> Optional[_Ctx]:
    """Snapshot for cross-thread propagation (fan-out pools)."""
    return _get_ctx()


def wire_context() -> Optional[Tuple[str, str]]:
    """(trace_id, parent_span_id) to put on an outgoing RPC frame."""
    ctx = _get_ctx()
    if ctx is None:
        return None
    return ctx.tid, ctx.sid


class _CtxGuard:
    """Context manager installing a _Ctx (or None) on this thread."""

    __slots__ = ("_ctx", "_prev")

    def __init__(self, ctx: Optional[_Ctx]):
        self._ctx = ctx

    def __enter__(self):
        self._prev = getattr(_tls, "ctx", None)
        _tls.ctx = self._ctx
        return self._ctx

    def __exit__(self, *exc):
        _tls.ctx = self._prev
        return False


def use_ctx(ctx: Optional[_Ctx]) -> _CtxGuard:
    """Re-establish a snapshot taken with current_ctx() on a pool
    thread (no-op guard when ctx is None).  Installs a COPY sharing the
    trace id and sink but owning its parent-span slot — span guards
    mutate `ctx.sid`, and concurrent branches of one query must not
    stomp each other's parenting (sink.append itself is atomic)."""
    if ctx is None:
        return _CtxGuard(None)
    return _CtxGuard(_Ctx(ctx.tid, ctx.sid, ctx.sink, ctx.service,
                          ctx.annotate))


class _SpanGuard:
    """Open span: on exit, append the finished record to the sink."""

    __slots__ = ("_ctx", "_rec", "_t0", "_prev_sid", "_ann")

    def __init__(self, ctx: Optional[_Ctx], name: str, attrs: Dict[str, Any]):
        self._ctx = ctx
        if ctx is None:
            return
        self._rec = {"tid": ctx.tid, "sid": f"s{_PROC}-{next(_span_seq)}",
                     "psid": ctx.sid, "name": name, "svc": ctx.service,
                     "t0": 0.0, "dur_us": 0}
        if attrs:
            self._rec["attrs"] = attrs

    def __enter__(self):
        ctx = self._ctx
        if ctx is None:
            return None
        self._prev_sid = ctx.sid
        ctx.sid = self._rec["sid"]
        self._ann = _annotate(self._rec["name"]) if ctx.annotate else None
        self._t0 = _now_ns()
        return self._rec

    def __exit__(self, exc_type, exc, tb):
        ctx = self._ctx
        if ctx is None:
            return False
        t1 = _now_ns()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        ctx.sid = self._prev_sid
        rec, t0 = self._rec, self._t0
        rec["t0"] = ((_EPOCH_OFFSET_NS + t0) // 1000) / 1e6
        rec["dur_us"] = (t1 - t0) // 1000
        if exc is not None:
            rec.setdefault("attrs", {})["error"] = \
                f"{type(exc).__name__}: {exc}"
        ctx.sink.append(rec)
        return False


def span(name: str, **attrs) -> _SpanGuard:
    """Child span of the active trace; no-op when none is active."""
    return _SpanGuard(_get_ctx(), name, attrs)


def _append(name: str, start_ns: int, dur_us: int, attrs: Dict[str, Any]):
    ctx = _get_ctx()
    if ctx is None:
        return
    rec = {"tid": ctx.tid, "sid": _new_id("s"), "psid": ctx.sid,
           "name": name, "svc": ctx.service, "t0": _epoch_s(start_ns),
           "dur_us": dur_us}
    if attrs:
        rec["attrs"] = attrs
    ctx.sink.append(rec)


def record_phase(name: str, start_s: float, dur_s: float, **attrs):
    """Append an interval the caller timed itself: `start_s` is its
    `time.perf_counter()` reading at the start, `dur_s` its length.
    For phases measured on another thread (a shared batched launch
    replayed into each lane's trace); code that can should use a
    `with span(...)` instead, which is also a profiler annotation."""
    _append(name, int(start_s * 1e9), int(dur_s * 1e6), attrs)


@contextmanager
def phase(phases: list, name: str, **attrs):
    """One device phase of a launch: a span LIVE where a trace is
    active (a solo statement's: the benchmark's trace reduction labels
    idle gaps by the spans open at a gap's midpoint and the phase ledger
    folds them) and a (name, perf_counter start, seconds, attrs) record
    in the launch's `phases` list, which a shared launch's members
    replay into their own traces (tpu/runtime.py `_attribute`): nothing
    is traced on the launcher's thread while the launch runs."""
    t0 = time.perf_counter()
    with span(name, **attrs):
        yield
    phases.append((name, t0, time.perf_counter() - t0, attrs))


def mark(name: str, **attrs):
    """A zero-length marker span at now (a retry, a breaker transition,
    a dedup hit): carries facts in its attrs, no time."""
    _append(name, _now_ns(), 0, attrs)


def graft(spans: List[dict]):
    """Merge spans returned by a remote service into the active trace
    (they already carry their own parentage — the root of the remote
    subtree points at the client-side rpc span id we sent over)."""
    ctx = _get_ctx()
    if ctx is None or not spans:
        return
    for s in spans:
        s = dict(s)
        s["remote"] = True
        ctx.sink.append(s)


class _TraceGuard:
    """Root context: owns the sink; stores the finished trace."""

    __slots__ = ("_ctx", "_rec", "_t0", "_prev", "_ann")

    def __init__(self, name: str, service: str, attrs: Dict[str, Any]):
        tid = _new_id("t")
        sink: List[dict] = []
        self._ctx = _Ctx(tid, "", sink, service)
        self._ann = None
        self._rec = {"tid": tid, "sid": _new_id("s"), "psid": "",
                     "name": name, "svc": service, "t0": 0.0,
                     "dur_us": 0}
        if attrs:
            self._rec["attrs"] = attrs

    @property
    def trace_id(self) -> str:
        return self._ctx.tid

    def set_name(self, name: str):
        """Name the root once the statement's kind is known (the root
        opens before the parse).  Its profiler annotation keeps the
        name it was entered under and takes this one as metadata."""
        self._rec["name"] = name
        if self._ann is not None:
            self._ann.set_metadata(name=name)

    def set(self, **attrs):
        self._rec.setdefault("attrs", {}).update(attrs)

    def __enter__(self):
        self._prev = getattr(_tls, "ctx", None)
        self._ctx.sid = self._rec["sid"]
        _tls.ctx = self._ctx
        if _profiling():
            self._ctx.annotate = True
            self._ann = _annotate(self._rec["name"])
        self._t0 = _now_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = _now_ns()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        _tls.ctx = self._prev
        rec, sink = self._rec, self._ctx.sink
        rec["t0"] = _epoch_s(self._t0)
        rec["dur_us"] = (t1 - self._t0) // 1000
        if exc is not None:
            rec.setdefault("attrs", {})["error"] = \
                f"{type(exc).__name__}: {exc}"
        sink.append(rec)
        spans = list(sink)
        if rec["name"].startswith("query:"):
            # the statement's closed time budget: ONE locked counter
            # update per statement, no per-span lock or stats() call
            us, n = fold_phases(spans)
            stats().inc_labeled_many(
                "phase", {PHASE_US: us, PHASE_N: n})
            # a mutating statement: entry to its LAST write request's
            # acknowledgement (the store marks each)
            acked = [s["t0"] for s in spans if s["name"] == WRITE_ACKED]
            if acked:
                stats().add_value("write_ack_s", max(acked) - rec["t0"])
        trace_store().add(self._ctx.tid, rec["name"], spans)
        return False


def start_trace(name: str, service: str = "standalone",
                **attrs) -> _TraceGuard:
    """Open a new root trace on this thread (a nested call opens a
    trace of its own).  A root named `query:*` is a statement: its
    spans' self times are folded into `stmt_phase_us{phase}` when it
    closes."""
    return _TraceGuard(name, service, attrs)


class _RemoteGuard:
    """Server-side adoption of an incoming wire context: spans produced
    while handling the RPC go to a FRESH sink that the dispatcher ships
    back in the reply — they are NOT stored locally (the coordinator
    owns the trace)."""

    __slots__ = ("_ctx", "_prev")

    def __init__(self, tid: str, psid: str, service: str):
        self._ctx = _Ctx(tid, psid, [], service, _profiling())

    @property
    def spans(self) -> List[dict]:
        return self._ctx.sink

    def __enter__(self):
        self._prev = getattr(_tls, "ctx", None)
        _tls.ctx = self._ctx
        return self

    def __exit__(self, *exc):
        _tls.ctx = self._prev
        return False


def adopt_remote(tid: str, psid: str, service: str) -> _RemoteGuard:
    return _RemoteGuard(tid, psid, service)


# -- the per-process store of finished traces -------------------------------


class TraceStore:
    """Bounded ring of recent traces, newest last."""

    def __init__(self, capacity: int = 256):
        self.capacity = capacity
        self._traces: Dict[str, dict] = {}   # insertion-ordered
        self._lock = threading.Lock()

    def add(self, tid: str, name: str, spans: List[dict]):
        # a trace's root closes last: found from the end
        root = next((s for s in reversed(spans) if not s.get("psid")), None)
        entry = {"tid": tid, "name": name,
                 "t0": root["t0"] if root else time.time(),
                 "dur_us": root["dur_us"] if root else 0,
                 "spans": spans}
        with self._lock:
            self._traces[tid] = entry
            while len(self._traces) > self.capacity:
                self._traces.pop(next(iter(self._traces)))

    def get(self, tid: str) -> Optional[dict]:
        with self._lock:
            return self._traces.get(tid)

    def list(self, limit: int = 50) -> List[dict]:
        """Newest-first summaries (no span bodies)."""
        with self._lock:
            entries = list(self._traces.values())
        return [{"tid": e["tid"], "name": e["name"], "t0": e["t0"],
                 "dur_us": e["dur_us"], "spans": len(e["spans"])}
                for e in reversed(entries[-limit:])]

    def clear(self):
        with self._lock:
            self._traces.clear()


def render_tree(entry: dict) -> str:
    """Indented text rendering of one trace's span tree.  Orphan spans
    (parent not shipped — e.g. a remote subtree whose local anchor was
    dropped) attach under the root rather than vanishing."""
    spans = entry["spans"]
    by_id = {s["sid"]: s for s in spans}
    children: Dict[str, List[dict]] = {}
    root = None
    for s in spans:
        psid = s.get("psid") or ""
        if not psid:
            root = s
            continue
        children.setdefault(
            psid if psid in by_id else "__orphan__", []).append(s)
    lines: List[str] = []

    def visit(s: dict, depth: int):
        attrs = s.get("attrs") or {}
        extra = "".join(f" {k}={v}" for k, v in sorted(attrs.items()))
        svc = s.get("svc", "")
        rem = " [remote]" if s.get("remote") else ""
        lines.append("  " * depth
                     + f"{s['name']} ({svc}{rem}) {s['dur_us']}us{extra}")
        for c in sorted(children.get(s["sid"], []), key=lambda x: x["t0"]):
            visit(c, depth + 1)

    if root is not None:
        visit(root, 0)
    for s in sorted(children.get("__orphan__", []), key=lambda x: x["t0"]):
        visit(s, 1)
    return "\n".join(lines)


# -- the statement phase ledger ---------------------------------------------

PHASE_US, PHASE_N = "stmt_phase_us", "stmt_phase_n"

#: the FIXED phase vocabulary (at most 20 labels): where a statement's
#: time went, by the self time of its spans.  `other` is the root's own
#: self time — what no child span explains.
# zero-length marker a store leaves when a write request of the statement
# is acknowledged, every part's reply in (cluster/dstore.py)
WRITE_ACKED = "storage:write_acked"

PHASES = ("parse", "plan", "admit", "exec", "snapshot_check", "delta_apply",
          "rpc_wait", "remote", "queue", "put", "dispatch", "fetch",
          "release", "materialise", "mat_concat", "mat_decode", "encode",
          "record", "other")

# span name -> phase, by the first prefix that matches; None = a
# zero-length marker that is not a unit of work.  What no prefix names
# reads `exec`: the executors' own Python and row assembly (`exec:*`,
# and `store:*` / `raft:*` run in-process) and the device runtime's
# host work around a launch (`tpu:prep`, `tpu:launch`, `tpu:seed_prep`,
# `tpu:launch_account`, `tpu:fetch_warm`).
_PHASE_BY_PREFIX = (
    ("graphd:parse", "parse"), ("graphd:plan", "plan"),
    ("graphd:admit", "admit"), ("graphd:encode", "encode"),
    # the statement's bookkeeping after its executor (`exec/engine.py`
    # `_execute_parsed`: result cache, slow log, insights, flight
    # recorder)
    ("graphd:record", "record"),
    ("tpu:snapshot_check", "snapshot_check"),
    # a fresh read folding acknowledged writes into the resident delta
    # plane (`TpuRuntime._try_delta_update`): its own bookkeeping, the
    # wait for the gate and the put; its census and key re-reads are
    # RPCs and read `rpc_wait` / `remote`
    ("tpu:delta_", "delta_apply"), ("device:delta_put", "delta_apply"),
    ("device:queue", "queue"), ("device:launch_wait", "queue"),
    ("device:put", "put"),
    ("device:dispatch", "dispatch"), ("device:fetch", "fetch"),
    # giving a rung's device result up, apart from its fetch
    ("device:release", "release"),
    # inside `device:materialise`, whose own self time stays
    # `materialise`: the fetched pieces joined into columns, and the
    # dense-to-vid and property decodes
    ("device:materialise.concat", "mat_concat"),
    ("device:materialise.decode", "mat_decode"),
    ("device:materialise", "materialise"),
    # a `CALL algo.*` statement (`algo/engine.py`): an iteration's own
    # time is its kernel's run (the gate's wait below it stays `queue`),
    # the flat edge list's build and sort are the host's work, the
    # uploads are puts and `assemble_rows` makes the result's rows
    ("tpu:algo_iter", "dispatch"), ("algo:prepare", "exec"),
    ("algo:put", "put"), ("algo:assemble", "materialise"),
    ("rpc:retry", None), ("rpc:breaker", None),
    ("storage:dedup_hit", None), ("storage:follower_read", None),
    (WRITE_ACKED, None),
    ("storage:", "rpc_wait"), ("rpc:", "rpc_wait"), ("meta:", "rpc_wait"),
    ("query:", "other"))
_phase_cache: Dict[str, Optional[str]] = {}


def phase_of(name: str) -> Optional[str]:
    ph = next((p for pre, p in _PHASE_BY_PREFIX if name.startswith(pre)),
              "exec")
    if len(_phase_cache) < 1024:
        _phase_cache[name] = ph
    return ph


def self_times(spans: List[dict]) -> Dict[str, float]:
    """sid -> self time in us, for every LOCAL span of one finished
    trace: the span's duration minus the union of its children's
    intervals, each clipped to it (NOT minus their sum: a fan-out's
    children overlap).  Where siblings overlap, each sibling's subtree
    is scaled by union / sum of that sibling group, so the wall time a
    fan-out shares is split in proportion to length and the self times
    of a trace sum to its root's duration.  Remote spans carry another
    host's clock and are left out (`fold_phases` places them inside
    their `rpc:` parent by their length alone); a span whose parent was
    not recorded hangs off the root.  One pass down the tree: this runs
    once per statement, under the GIL every session shares."""
    kids: Dict[str, List[dict]] = {}
    sids = set()
    root = None
    for s in spans:
        if s.get("remote"):
            continue
        sids.add(s["sid"])
        p = s["psid"]
        if not p:
            root = s
        elif p in kids:
            kids[p].append(s)
        else:
            kids[p] = [s]
    if root is None:
        return {}
    for p in [p for p in kids if p not in sids]:
        kids.setdefault(root["sid"], []).extend(kids.pop(p))
    out: Dict[str, float] = {}
    a = round(root["t0"] * 1e6)
    # (span, start, end, weight) of the spans that have spans below
    # them; a leaf's self time is its clipped length, set where its
    # parent places it (most spans of a statement are leaves)
    stack = [(root, a, a + root["dur_us"], 1.0)]
    while stack:
        s, a, b, w = stack.pop()
        covered = 0
        ivs = []
        for c in kids.get(s["sid"], ()):
            ca = round(c["t0"] * 1e6)
            cb = ca + c["dur_us"]
            if cb > b:                      # clipped to its parent
                cb = b
            if ca < a:
                ca = a
            elif ca > b:
                ca = b
            if cb > ca:
                ivs.append((ca, cb, c))
            elif c["sid"] in kids:
                # clipped away, or empty: it reads 0, and so does
                # whatever is open below it
                stack.append((c, ca, ca, w))
            else:
                out[c["sid"]] = 0.0         # a marker
        if ivs:
            f = w
            if len(ivs) == 1:
                covered = ivs[0][1] - ivs[0][0]
            else:
                ivs.sort(key=_by_start)
                total, end = 0, a
                for ca, cb, c in ivs:
                    total += cb - ca
                    if cb > end:
                        covered += cb - max(ca, end)
                        end = cb
                if covered != total:        # siblings overlap
                    f = w * covered / total
            for ca, cb, c in ivs:
                if c["sid"] in kids:
                    stack.append((c, ca, cb, f))
                else:
                    out[c["sid"]] = (cb - ca) * f
        out[s["sid"]] = (b - a - covered) * w
    return out


def _by_start(iv):
    return iv[0]


def fold_phases(spans: List[dict]
                ) -> Tuple[Dict[str, int], Dict[str, int]]:
    """One finished statement trace -> ({phase: self us}, {phase:
    spans}).  The us sum to the root's duration.  An `rpc:` span's self
    time is transport and queueing; the share of it that its grafted
    handler span's LENGTH covers goes to `remote` (another host's clock
    is not ours, so only the length is used).  `rpc_wait` counts `rpc:`
    spans only: a `storage:` span wraps one and is not counted again."""
    selfs = self_times(spans)
    us: Dict[str, float] = {}
    n: Dict[str, int] = {}
    handler: Dict[str, int] = {}
    rpcs = []
    for s in spans:
        if s.get("remote"):
            if s["psid"] in selfs:
                handler[s["psid"]] = handler.get(s["psid"], 0) + s["dur_us"]
            continue
        name = s["name"]
        ph = _phase_cache.get(name, "")
        if ph == "":
            ph = phase_of(name)
        if ph is None:
            continue
        if ph != "rpc_wait":
            n[ph] = n.get(ph, 0) + 1
        elif name.startswith("rpc:"):
            n[ph] = n.get(ph, 0) + 1
            rpcs.append(s)
        us[ph] = us.get(ph, 0.0) + selfs[s["sid"]]
    for s in rpcs:
        h = handler.get(s["sid"])
        if h:
            part = selfs[s["sid"]] * min(1.0, h / max(s["dur_us"], 1))
            us["remote"] = us.get("remote", 0.0) + part
            us["rpc_wait"] -= part
            n["remote"] = n.get("remote", 0) + 1
    return {k: round(v) for k, v in us.items()}, n


_store = TraceStore()


def trace_store() -> TraceStore:
    """The process-wide store (each daemon serves it at /traces)."""
    return _store
