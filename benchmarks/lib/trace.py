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
statement, send to last row).
"""
from __future__ import annotations

import glob
import os

SLICE_BEGIN, SLICE_END, STMT = "bench:slice_begin", "bench:slice_end", "bench:stmt"
DEVICE_PLANE, DEVICE_LINE = "/device:TPU:", "XLA Ops"
NAME_CHARS = 120      # an operation's name is its HLO text: keep its head


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> dict:
    """-> {"devices": {plane: [(name, start_ns, end_ns)]}, "marks":
    {name: [(start_ns, end_ns)]}} — everything the reduction needs, as
    plain tuples (so that a test can build one by hand)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes = list(pd.planes)
    on_chip = [p for p in planes if p.name.startswith(DEVICE_PLANE)]
    devices, marks = {}, {SLICE_BEGIN: [], SLICE_END: [], STMT: []}
    for p in planes:
        for ln in p.lines:
            dev_line = p in on_chip and ln.name == DEVICE_LINE
            for e in ln.events:
                name = e.name
                if name in marks:
                    marks[name].append((e.start_ns, e.start_ns + e.duration_ns))
                elif dev_line:
                    devices.setdefault(p.name, []).append(
                        (name, e.start_ns, e.start_ns + e.duration_ns))
                elif not on_chip and e.duration_ns > 0 and \
                        any(k == "hlo_op" for k, _ in e.stats):
                    devices.setdefault("/host:CPU (hlo_op events)", []).append(
                        (name, e.start_ns, e.start_ns + e.duration_ns))
    return {"devices": devices, "marks": marks,
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


def reduce(loaded: dict, sessions: int = 1, top: int = 10, gaps: int = 5) -> dict:
    """-> busy_s (mean over the device planes), window_s, idle_share (%),
    device_ops [[name, s]], idle_gaps [[what the host was doing, s]]."""
    marks = loaded["marks"]
    every = [iv for evs in loaded["devices"].values() for iv in evs]
    if marks[SLICE_BEGIN] and marks[SLICE_END]:
        t0, t1 = marks[SLICE_BEGIN][0][0], marks[SLICE_END][-1][0]
    elif every:
        t0, t1 = min(s for _, s, _ in every), max(e for _, _, e in every)
    else:
        return {"busy_s": 0.0, "window_s": 0.0, "idle_share": None,
                "device_ops": [], "idle_gaps": [], "planes": loaded.get("planes", [])}
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
    # gaps: where NO device ran anything, labelled by the statement spans
    merged = union(merged_all)
    stmts = sorted(marks[STMT])
    holes, cur = [], t0
    for s, e in merged + [[t1, t1]]:
        if s > cur:
            holes.append((cur, s))
        cur = max(cur, e)
    holes.sort(key=lambda h: h[0] - h[1])
    labelled = []
    for s, e in holes[:gaps]:
        labelled.append([_host_state(s, e, stmts, merged, sessions), (e - s) / 1e9])
    return {"busy_s": busy_s, "window_s": window_s,
            "idle_share": 100.0 * (1.0 - busy_s / window_s) if window_s > 0 else None,
            "device_ops": [[n[:NAME_CHARS], d / 1e9] for n, d in ops],
            "idle_gaps": labelled, "planes": loaded.get("planes", [])}


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
