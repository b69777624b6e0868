"""Reference operation `bfs_levels`: the device half of FIND SHORTEST PATH
FROM start TO target OVER E UPTO max_steps STEPS, which is also LDBC
Graphalytics' BFS output: the level of EVERY vertex from `start` over the
out-edges of `over` (0 the start itself, -1 beyond `max_steps`), one
integer a vertex in vid order, compared position by position.

A level-synchronous BFS over reference/graph.py's CSR with a boolean
visited mask.  One run a start is kept (eight candidates are drawn for
every request and each is then asked for again by the check), for the graph
last seen alone: a second graph empties the memo.  A run also keeps the
size and the out-edges of every frontier it expanded, from which
lib/bfs_bytes.py reckons what a BFS has to move (`profile`).  The target
($t, for the statement's text only: the cell stops at the levels) is the
smallest vid on the deepest level reached.  numpy only; imports nothing
of the program."""
from __future__ import annotations

import weakref

import numpy as np

from benchmarks.reference.graph import _slots

_memo = {"graph": None, "runs": {}}


def levels(ref, over, start, max_steps):
    """-> (level of every vertex as int8, [frontier size, out-edges of
    the frontier] per level expanded, all `max_steps` of them: a level
    with nothing left to expand expands nothing)."""
    n = ref.n
    level = np.full(n, -1, np.int8)
    level[start] = 0
    frontier = np.asarray([start], np.int64)
    expanded = []
    for depth in range(1, int(max_steps) + 1):
        seen = np.zeros(n, bool)
        edges = 0
        for et in over:
            csr = ref.csr[et]
            slots, _ = _slots(csr, frontier)
            edges += int(slots.size)
            seen[csr.nbr[slots]] = True
        expanded.append([int(frontier.size), edges])
        seen &= level < 0
        frontier = np.flatnonzero(seen)
        level[frontier] = depth
    return level, expanded


def _run(ref, t, start):
    held = _memo["graph"]
    if held is None or held() is not ref:
        _memo.update(graph=weakref.ref(ref), runs={})
    key = (tuple(t["over"]), int(t["max_steps"]), int(start))
    if key not in _memo["runs"]:
        _memo["runs"][key] = levels(ref, t["over"], int(start), t["max_steps"])
    return _memo["runs"][key]


def profile(t, start):
    """([frontier size, out-edges] per level the reference expanded from
    `start` on the graph last seen, that graph's vertices), or None if it
    never ran from there."""
    run = _memo["runs"].get((tuple(t["over"]), int(t["max_steps"]), int(start)))
    return None if run is None else (run[1], int(run[0].size))


def params(ref, t, start):
    level = _run(ref, t, start)[0]
    return {"$t": str(int((level == level.max()).argmax()))}


def answer(ref, t, start):
    return {"level": _run(ref, t, start)[0]}


def count(ref, t, start):
    """Rows of the reply: the vertices with a level (the start among them)."""
    return int((_run(ref, t, start)[0] >= 0).sum())


def compare(reply, want):
    """-> (vertices whose level differs, None, detail): by position, so a
    level moved from one vertex to another is two that differ."""
    got, level = np.asarray(reply.column("level")), want["level"]
    if got.shape != level.shape:
        return max(abs(got.size - level.size), 1), None, f"{got.size} levels != {level.size}"
    bad = int((got.astype(np.int64) != level).sum())
    return bad, None, f"{level.size} levels, {bad} differ"
