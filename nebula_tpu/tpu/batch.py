"""Multi-query batch former: drain concurrent compatible dispatches
into one padded multi-lane kernel launch (ISSUE 15 tentpole).

Under concurrency every small GO/MATCH statement used to pay its own
device dispatch — the PR 7 concurrency bench measures `queue_wait_share`
for exactly that, and the PR 8 admission wait queue already RELEASES
compatible statements in bursts that nobody exploited.  This module is
the missing half: when K statements that would compile to the SAME
device program (kernel family + shape bucket + predicate/yield program
— the compatibility key the runtime derives from its jit-cache key)
reach the dispatch boundary together, they enroll in a forming GROUP;
after a bounded `batch_wait_us` window (or as soon as the group fills
to `batch_max_lanes`) ONE member launches a single lane-batched kernel
(`hop.build_traverse_fn(..., lanes=True)`: the vmapped program on a
single chip, the lanes × shards grid program on a multi-device mesh —
PR 17) for everyone, and each member de-muxes its own lane back
out through the per-statement attribution machinery (rows, WorkCounters,
cost sinks, flight entries stay exactly per-statement — the PR 7
concurrent-attribution contract).

Mesh composition (PR 17): the compatibility key the runtime submits
INCLUDES the mesh identity — (lanes, parts, mesh epoch) via
`TpuRuntime._mesh_key()` — so a `set_mesh` re-shard mid-form can never
merge lanes compiled for different launch grids: members enrolled
against the old grid keep their group (its key names the old epoch)
while post-re-shard arrivals form a NEW group under the bumped epoch.
If the old group's launch runs after the re-shard donated its
snapshot's buffers, the runtime's retired-snapshot check surfaces
TpuUnavailable to every member, which take their usual re-pin/host
fallback — never a silently merged cross-grid launch.

Design points:

  * `batch_max_lanes = 0` (the default) is the OFF switch — the former
    is never consulted and the dispatch path is byte-identical to the
    pre-batching runtime.
  * No dedicated thread and no leader hand-off: every member waits on
    the group condition; whichever member's wait expires first CLAIMS
    the launch (group state FORMING → LAUNCHING → DONE).  A member
    killed or deadline-expired while FORMING withdraws (its lane never
    launches); once LAUNCHING, a cancelled member's lane rides along
    and its result is simply discarded at de-mux — batchmates complete
    unaffected either way.
  * Single-query latency is preserved: a statement only waits the
    forming window when there is EVIDENCE of concurrency — another
    forming group member, >1 live statement, or a recent multi-
    statement admission drain burst (`AdmissionController.
    concurrency_hint()`, the admission→former hand-off).  A lone
    statement takes the solo dispatch path untouched.
  * One batched launch consumes ONE dispatch-table slot (the launcher's
    `_gated_dispatch`), so `tpu_dispatch_queue_cap` judges batches, not
    lanes — turning batching ON can only DECREASE the host-shed rate
    (ISSUE 15 satellite; regression-tested).

Metrics: `tpu_batches_formed`, `tpu_batch_lanes`,
`tpu_batch_form_wait_us`; span `tpu:batch` (emitted by the runtime's
escalation driver); failpoint `tpu:batch_form` at the enrollment boundary
(`raise` = this statement dispatches solo, `delay` = held forming).
Docs: docs/PERFORMANCE.md §10, docs/OBSERVABILITY.md catalogues,
docs/ROBUSTNESS.md failpoint table.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

from ..utils import cancel as _cancel
from ..utils.config import define_flag, get_config
from ..utils.failpoints import fail

define_flag("batch_max_lanes", 0,
            "max statements coalesced into one multi-lane device "
            "launch; 0/1 = batching OFF (byte-identical to the "
            "pre-batching dispatch path); runtime-updatable via "
            "UPDATE CONFIGS")
define_flag("batch_wait_us", 1500,
            "bounded batch-forming window: a dispatch with concurrent "
            "compatible company waits at most this long for "
            "batchmates before launching (the group launches early "
            "the moment it fills to batch_max_lanes); runtime-"
            "updatable via UPDATE CONFIGS")

_FORMING, _LAUNCHING, _DONE = 0, 1, 2


class _Member:
    __slots__ = ("dense", "withdrawn", "lane", "t_enq", "live")

    def __init__(self, dense: Sequence[int], live):
        self.dense = list(dense)
        self.withdrawn = False
        self.lane: Optional[int] = None   # assigned at launch claim
        self.t_enq = time.monotonic()
        self.live = live


class _Group:
    __slots__ = ("key", "bid", "cond", "state", "deadline", "ready",
                 "members", "res", "info", "error", "t_launch")

    def __init__(self, key, bid: int, deadline: float):
        self.key = key
        self.bid = bid
        self.cond = threading.Condition()
        self.state = _FORMING
        self.deadline = deadline
        self.ready = False            # filled to batch_max_lanes
        self.members: List[_Member] = []
        self.res = None
        self.info: Optional[Dict[str, Any]] = None
        self.error: Optional[BaseException] = None
        self.t_launch = deadline


class LaneResult:
    """One statement's slice of a shared launch: the lane index into
    the batched result arrays plus the launch-level info the runtime
    needs for per-lane attribution."""

    __slots__ = ("lane", "res", "info", "form_wait_us", "lanes",
                 "batch_id")

    def __init__(self, lane: int, res, info, form_wait_us: int,
                 lanes: int, batch_id: int):
        self.lane = lane
        self.res = res
        self.info = info
        self.form_wait_us = form_wait_us
        self.lanes = lanes
        self.batch_id = batch_id


class BatchFormer:
    """Process-wide: groups compatible in-flight dispatches per key and
    runs each group as one lane-batched launch."""

    #: waiter poll slice while forming/awaiting launch — the cadence of
    #: the KILL/deadline re-check (same rationale as the admission
    #: controller's POLL_S: "detaches immediately" stays honest)
    POLL_S = 0.005

    def __init__(self):
        self._mu = threading.Lock()
        self._groups: Dict[Any, _Group] = {}
        self._bid = 0

    # -- flags ------------------------------------------------------------

    @staticmethod
    def _flag_int(name: str, dflt: int) -> int:
        try:
            return int(get_config().get(name))
        except Exception:  # noqa: BLE001 — config not initialized
            return dflt

    def max_lanes(self) -> int:
        return self._flag_int("batch_max_lanes", 0)

    def wait_s(self) -> float:
        return max(self._flag_int("batch_wait_us", 1500), 0) / 1e6

    def enabled(self) -> bool:
        # one lane cannot share anything: <=1 is the off sentinel
        return self.max_lanes() > 1

    # -- the admission→former hand-off ------------------------------------

    @staticmethod
    def _concurrency_hint() -> bool:
        """Is there evidence that batchmates may arrive?  Without any,
        the statement dispatches solo with ZERO added latency — the
        forming window only ever delays statements that provably have
        concurrent company."""
        from ..utils.workload import live_registry
        if len(live_registry()) > 1:
            return True
        from ..utils.admission import admission
        return admission().concurrency_hint()

    # -- enrollment --------------------------------------------------------

    def submit(self, key, dense: Sequence[int],
               launch: Callable[[List[List[int]]], Any],
               kernel: str = "traverse",
               gate_busy: Optional[Callable[[], bool]] = None
               ) -> Optional[LaneResult]:
        """Enroll one dispatch under `key`.  Returns the statement's
        LaneResult after the shared launch, or None when the caller
        should dispatch solo (batching off / no concurrency evidence /
        lost a forming race).  `launch(lane_dense)` runs the actual
        lane-batched escalation and returns (res, info) — called by
        exactly ONE member per group.  Raises QueryKilled /
        DeadlineExceeded when THIS statement is cancelled (mid-form:
        its lane withdraws before launch; mid-flight: its lane's
        result is discarded) and re-raises the launch error to every
        member when the shared launch fails.

        `gate_busy` (optional) probes the runtime's dispatch gate: a
        group whose forming window expires while a writer holds the
        gate (re-pin / delta apply / compaction swap) RE-ARMS the
        window instead of launching — launching would only queue the
        fully-formed batch behind the hold with `batch_wait_us`
        already spent, while statements arriving during the hold piled
        into fresh groups (ISSUE 19 satellite)."""
        max_lanes = self.max_lanes()
        if max_lanes <= 1:
            return None
        # failpoint at the enrollment boundary: `raise` rejects
        # batching for this statement (it dispatches solo — never
        # wrong, never host-fallback), `delay` holds it here
        fail.hit("tpu:batch_form", key=kernel)
        with self._mu:
            g = self._groups.get(key)
            join = (g is not None and g.state == _FORMING
                    and len(g.members) < max_lanes)
            if not join and not self._concurrency_hint():
                return None     # solo fast path: no company, no wait
            if not join:
                self._bid += 1
                g = _Group(key, self._bid,
                           time.monotonic() + self.wait_s())
                self._groups[key] = g
            from ..utils.workload import current_live
            lv = current_live()
            m = _Member(dense, lv)
            g.members.append(m)
            lane_provisional = len(g.members) - 1
            if len(g.members) >= max_lanes:
                g.ready = True
        if lv is not None:
            # SHOW QUERIES shows BatchId/lane while enrolled (ISSUE 15
            # satellite); the launch claim re-stamps the final lane
            lv.batch_id, lv.lane = g.bid, lane_provisional
        try:
            return self._wait_and_demux(key, g, m, launch, kernel,
                                        gate_busy)
        finally:
            if lv is not None:
                lv.batch_id, lv.lane = None, None

    def _wait_and_demux(self, key, g: _Group, m: _Member, launch,
                        kernel: str, gate_busy=None
                        ) -> Optional[LaneResult]:
        launcher = False
        with g.cond:
            while g.state != _DONE:
                if g.state == _FORMING and (
                        g.ready or time.monotonic() >= g.deadline):
                    if not g.ready and gate_busy is not None \
                            and gate_busy():
                        # window expired under a write-gate hold: re-arm
                        # so the group keeps forming through the hold
                        # and gets a FRESH window once the gate frees
                        # (a full group skips this — it cannot grow, so
                        # it may as well queue at the gate).  One waiter
                        # moves the deadline per expiry: the loop holds
                        # g.cond, so re-arms are serialized.
                        g.deadline = time.monotonic() + self.wait_s()
                        from ..utils.stats import stats
                        stats().inc("tpu_batch_gate_rearms")
                        continue
                    g.state = _LAUNCHING
                    launcher = True
                    break
                kill = _cancel.current_kill()
                if kill is not None and kill.is_set():
                    forming = g.state == _FORMING
                    self._withdraw(key, g, m)
                    raise _cancel.QueryKilled(
                        "query was killed while batch-forming"
                        if forming else
                        "query was killed awaiting a batched launch")
                rem = _cancel.remaining()
                if rem is not None and rem <= 0:
                    self._withdraw(key, g, m)
                    raise _cancel.DeadlineExceeded(
                        "deadline exhausted while batch-forming")
                timeout = self.POLL_S
                if g.state == _FORMING and not g.ready:
                    timeout = min(timeout, max(
                        g.deadline - time.monotonic(), 0.0) + 1e-4)
                g.cond.wait(timeout)
        if launcher:
            self._launch(key, g, launch, kernel)
        return self._demux(g, m)

    def _withdraw(self, key, g: _Group, m: _Member):
        """Mark a forming member withdrawn (caller holds g.cond and
        raises right after).  A group left with NO live members has no
        future launcher — remove it from the forming map so the next
        compatible statement opens a FRESH group instead of joining an
        expired husk (and so space/epoch-churned keys cannot leak
        all-withdrawn groups).  Taking self._mu under g.cond is safe:
        no thread ever blocks on g.cond while holding self._mu."""
        m.withdrawn = True
        if g.state == _FORMING and all(mm.withdrawn
                                       for mm in g.members):
            g.state = _DONE
            with self._mu:
                if self._groups.get(key) is g:
                    del self._groups[key]
            g.cond.notify_all()

    def _demux(self, g: _Group, m: _Member) -> Optional[LaneResult]:
        # -- DONE: de-mux ---------------------------------------------
        if g.error is not None:
            # shared failure (escalation non-convergence, device fault):
            # every member surfaces the same error; executors apply
            # their usual fallback contract to it
            raise g.error
        kill = _cancel.current_kill()
        if kill is not None and kill.is_set():
            # mid-flight cancel: the lane launched, its result is
            # discarded right here — batchmates are untouched
            raise _cancel.QueryKilled("query was killed")
        rem = _cancel.remaining()
        if rem is not None and rem <= 0:
            raise _cancel.DeadlineExceeded(
                "deadline exhausted during a batched launch")
        if m.lane is None:
            # joined in the claim race window after lanes were frozen:
            # not part of the launch — dispatch solo instead
            return None
        from ..utils.stats import stats
        form_wait_us = int(max(g.t_launch - m.t_enq, 0.0) * 1e6)
        stats().observe("tpu_batch_form_wait_us", form_wait_us)
        return LaneResult(m.lane, g.res, g.info, form_wait_us,
                          lanes=g.info["lanes"] if g.info else 1,
                          batch_id=g.bid)

    def _launch(self, key, g: _Group, launch, kernel: str):
        """Run the shared launch for every non-withdrawn member.  The
        claiming member executes on its own thread; per-statement TLS
        attribution is suppressed inside (the runtime's
        `_try_batched`), and each member attributes its own lane at
        de-mux."""
        with self._mu:
            if self._groups.get(key) is g:
                del self._groups[key]   # new arrivals form a new group
        with g.cond:
            lanes = [mm for mm in g.members if not mm.withdrawn]
            for i, mm in enumerate(lanes):
                mm.lane = i
                if mm.live is not None:
                    mm.live.lane = i
                    if len(lanes) > 1:
                        # lane share for the insights registry (ISSUE
                        # 16): how many statements this launch was
                        # amortized across
                        mm.live.batch_lanes = len(lanes)
        g.t_launch = time.monotonic()
        try:
            if len(lanes) > 1:
                from ..utils.stats import stats
                stats().inc("tpu_batches_formed")
                stats().observe("tpu_batch_lanes", len(lanes))
                g.res, g.info = launch([mm.dense for mm in lanes])
            else:
                # a 1-lane "batch" shares nothing: leave res unset —
                # the lone member falls back to the SOLO dispatch path
                # (solo jit cache, no lane program, no batch metrics),
                # so a too-short forming window costs only the window
                for mm in lanes:
                    mm.lane = None
        except BaseException as ex:  # noqa: BLE001 — fan the error out
            g.error = ex
        finally:
            with g.cond:
                g.state = _DONE
                g.cond.notify_all()

    # -- introspection / tests ---------------------------------------------

    def forming(self) -> Dict[Any, int]:
        """key → enrolled member count of currently-forming groups."""
        with self._mu:
            return {k: len(g.members) for k, g in self._groups.items()
                    if g.state == _FORMING}

    def reset(self):
        """Test isolation: abandon forming groups.  Enrolled members
        wake with no lane assigned and fall back to solo dispatch
        (submit returns None) — nothing blocks, nothing errors."""
        with self._mu:
            groups = list(self._groups.values())
            self._groups.clear()
        for g in groups:
            with g.cond:
                if g.state == _FORMING:
                    g.state = _DONE
                    g.cond.notify_all()


_former = BatchFormer()


def batch_former() -> BatchFormer:
    """The process-wide former (the runtime submits; tests introspect)."""
    return _former
