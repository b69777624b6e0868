"""Reduction of a profiler trace (.xplane.pb) to what the per-layer
metrics read: device busy seconds as the union of device-operation
intervals, the idle share, the operations that took most time, and the
longest device gaps with what the harness knows of the host then.

Read with `jax.profiler.ProfileData.from_file` (jax alone).  On a TPU the
device operations are the events of the line `XLA Ops` on each plane
`/device:TPU:<n>`; on the CPU backend (rehearsals, the recorded test
trace) there is no device plane and the operations are the host-plane
events that carry an `hlo_op` stat.

The harness writes three kinds of host spans into the same trace with
`jax.profiler.TraceAnnotation`: `bench:slice_begin` and `bench:slice_end`
(instants that bound the traced slice) and `bench:stmt` (one per
statement, send to last row).  The program writes its own spans there
too while a profiler session collects (`nebula_tpu/utils/trace.py`): one
event per span on the `/host:CPU` line of the thread that ran it, nested
as the calls were.  `load()` keeps them, each with its line, and
`reduce()` names every idle gap by the phases of the innermost spans
open in it.
"""
from __future__ import annotations

import glob
import os
import re

from nebula_tpu.utils.trace import PHASES, phase_of as _program_phase_of

SLICE_BEGIN, SLICE_END, STMT = "bench:slice_begin", "bench:slice_end", "bench:stmt"
DEVICE_PLANE, DEVICE_LINE = "/device:TPU:", "XLA Ops"
HOST_PLANE = "/host:CPU"
NAME_CHARS = 120      # an operation's name is its HLO text: keep its head
# The program's spans (`nebula_tpu/utils/trace.py`) are told from the
# host plane's other events by the SHAPE of their name, `<layer>:<what>`
# with the layer a lowercase word or dotted words (`graphd:parse`,
# `rpc.server:storage.part_stats`), not by a list of layers kept here: a
# span under a layer that a later program PR brings is labelled with no
# edit of this file.  What else the profiler writes there is named
# otherwise (`PjitFunction(fn)`, `TpuLoadedExecutable::ExecuteLaunch`,
# `H2D Dispatch`, `end: dot.1`, an HLO instruction's text: 84 distinct
# names in a traced chip run of PR 36, 27 of them spans by this rule and
# by the list it replaced alike).  The TPU runtime's own `tpu::System::*`
# events have no name after their first colon and are not spans; nor are
# the harness's own `bench:*` marks.
_SPAN = re.compile(r"[a-z][a-z0-9_]*(?:\.[a-z][a-z0-9_]*)*:[^:\s]")
NOT_SPANS = ("bench:",)


def is_span(name: str) -> bool:
    return _SPAN.match(name) is not None and not name.startswith(NOT_SPANS)


def phase_of(name: str):
    """A span's phase in the program's own vocabulary (`utils/trace.py`
    `phase_of`: the same map that folds the `graphd.*` phase counters, so
    a gap's label and those metrics cannot disagree), but for a handler's
    span, which the program books by its length inside its `rpc:` parent
    and which reads `remote` here.  None for a zero-length marker; a span
    of a layer the program's map does not name reads what that map gives
    it (`exec`).  The order of PHASES is the order of a gap's label."""
    return "remote" if name.startswith("rpc.server:") else _program_phase_of(name)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> dict:
    """-> {"devices": {plane: [(name, start_ns, end_ns)]}, "spans":
    [(name, line, start_ns, end_ns)], "marks": {name: [(start_ns,
    end_ns)]}, "planes": [name]} — the whole trace as the readers and
    the reduction need it, as plain tuples (so that a test can build one
    by hand).  `devices` keeps each device plane's operations apart;
    `spans` are the program's, `line` naming the host thread's line."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes = list(pd.planes)
    on_chip = [p for p in planes if p.name.startswith(DEVICE_PLANE)]
    devices, spans, marks = {}, [], {SLICE_BEGIN: [], SLICE_END: [], STMT: []}
    for p in planes:
        host = p.name == HOST_PLANE
        for i, ln in enumerate(p.lines):
            dev_line = p in on_chip and ln.name == DEVICE_LINE
            line = f"{ln.name}#{i}"
            for e in ln.events:
                name = e.name
                if name in marks:
                    marks[name].append((e.start_ns, e.start_ns + e.duration_ns))
                elif dev_line:
                    devices.setdefault(p.name, []).append(
                        (name, e.start_ns, e.start_ns + e.duration_ns))
                elif host and is_span(name):
                    if name.startswith("query:"):
                        # a root is entered before its kind is known and
                        # takes its name as metadata once it is
                        name = next((v for k, v in e.stats if k == "name"), name)
                    spans.append((name, line, e.start_ns, e.start_ns + e.duration_ns))
                elif not on_chip and e.duration_ns > 0 and \
                        any(k == "hlo_op" for k, _ in e.stats):
                    devices.setdefault("/host:CPU (hlo_op events)", []).append(
                        (name, e.start_ns, e.start_ns + e.duration_ns))
    return {"devices": devices, "spans": spans, "marks": marks,
            "planes": [p.name for p in planes]}


def union(intervals):
    """Merge [start, end) intervals -> sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def window(loaded: dict):
    """-> (t0, t1) of the traced slice in ns: between the harness's two
    instants, else from the first device operation to the last; None
    where the trace holds neither."""
    marks = loaded["marks"]
    if marks[SLICE_BEGIN] and marks[SLICE_END]:
        return marks[SLICE_BEGIN][0][0], marks[SLICE_END][-1][0]
    every = [iv for evs in loaded["devices"].values() for iv in evs]
    if every:
        return min(s for _, s, _ in every), max(e for _, _, e in every)
    return None


def leaves_at(spans, t):
    """The program's spans open at `t` that have no span open inside
    them on their own thread's line: one per line, the innermost."""
    inner = {}
    for sp in spans:
        _, line, s, e = sp
        if s <= t < e and (line not in inner or s >= inner[line][2]):
            inner[line] = sp
    return list(inner.values())


def reduce(loaded: dict, sessions: int = 1, top: int = 10, gaps: int = 5) -> dict:
    """-> busy_s (mean over the device planes), window_s, idle_share (%),
    device_ops [[name, s]], idle_gaps [[what the host was doing, s]]."""
    marks = loaded["marks"]
    bounds = window(loaded)
    if bounds is None:
        return {"busy_s": 0.0, "window_s": 0.0, "idle_share": None,
                "device_ops": [], "idle_gaps": [], "planes": loaded.get("planes", [])}
    t0, t1 = bounds
    busy, per_op, merged_all = [], {}, []
    for evs in loaded["devices"].values():
        clipped = [(max(s, t0), min(e, t1)) for _, s, e in evs if e > t0 and s < t1]
        merged = union(clipped)
        busy.append(sum(e - s for s, e in merged))
        merged_all.extend(merged)
        for name, s, e in evs:
            d = min(e, t1) - max(s, t0)
            if d > 0:
                per_op[name] = per_op.get(name, 0) + d
    n_dev = max(len(busy), 1)
    busy_s = sum(busy) / n_dev / 1e9
    window_s = (t1 - t0) / 1e9
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    # gaps: where NO device ran anything, labelled by the phases of the
    # program's innermost open spans, then by the statement spans
    merged = union(merged_all)
    stmts = sorted(marks[STMT])
    spans = loaded.get("spans", [])
    holes, cur = [], t0
    for s, e in merged + [[t1, t1]]:
        if s > cur:
            holes.append((cur, s))
        cur = max(cur, e)
    holes.sort(key=lambda h: h[0] - h[1])
    labelled = []
    for s, e in holes[:gaps]:
        state = _host_state(s, e, stmts, merged, sessions)
        open_phases = _open_phases(spans, (s + e) / 2)
        labelled.append([f"{open_phases}; {state}" if open_phases else state, (e - s) / 1e9])
    return {"busy_s": busy_s, "window_s": window_s,
            "idle_share": 100.0 * (1.0 - busy_s / window_s) if window_s > 0 else None,
            "device_ops": [[n[:NAME_CHARS], d / 1e9] for n, d in ops],
            "idle_gaps": labelled, "planes": loaded.get("planes", [])}


def _open_phases(spans, t):
    """`16xrpc_wait 4xsnapshot_check 1xencode`: how many of the spans
    open at `t` with nothing open inside them are of each phase."""
    n = {}
    for name, *_ in leaves_at(spans, t):
        ph = phase_of(name)
        if ph is not None:
            n[ph] = n.get(ph, 0) + 1
    return " ".join(f"{n[ph]}x{ph}" for ph in PHASES if ph in n)


def _host_state(s, e, stmts, merged, sessions):
    """What the harness knows of the host during a device gap."""
    mid = (s + e) / 2
    live = [(a, b) for a, b in stmts if a <= mid < b]
    if not live:
        return "between statements"
    if sessions > 1 or len(live) > 1:
        return f"{len(live)} statements in flight"
    a, b = live[0]
    before = any(a <= x < s for x, _ in merged)
    after = any(e <= x < b for x, _ in merged)
    if not before:
        return "inside a statement, before its first device operation"
    if not after:
        return "inside a statement, after its last device operation"
    return "inside a statement, between device operations"
