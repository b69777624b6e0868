"""Reference operation `bfs_levels_wide`: `bfs_levels`' semantics (the
level of EVERY vertex from `start` over the out-edges of `over`, 0 the
start itself, -1 beyond `max_steps`, one integer a vertex in vid order,
compared position by position by `bfs_levels`' own `compare`) at a size
where a level's frontier is a large share of the graph.

`bfs_levels.levels` lays every expanded slot out as three int64 arrays
(reference/graph.py `_slots`): 4 to 24 s a start over 180 M rows, and a
run asks for 48 starts.  Here a level whose frontier owns more than
1 / `WIDE` of an edge type's rows takes ONE pass over all of them
instead: a row marks its far end where its source is a member of the
frontier (`seen[nbr[member_of_row]] = True`), in vertex ranges handed to
a few threads (numpy lets go of the interpreter inside `repeat`, the
boolean take and the scatter; every thread writes the same `True`).  A
narrower level is `_slots`'.  Either way `seen` is the same bitmap, so
the levels and the profile are `bfs_levels`', integer for integer
(tests/benchmark/test_mesh_bfs_cell.py holds the two against each
other).  One run a start is kept for the graph last seen, as there, with
the size and the out-edges of every frontier expanded (`profile`, which
lib/bfs_bytes.py reads).  numpy only; imports nothing of the program."""
from __future__ import annotations

import os
import weakref
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmarks.reference.graph import _slots
from benchmarks.reference.ops.bfs_levels import compare  # noqa: F401 — this operation's too

WIDE = 8            # a frontier with more than rows / WIDE out-edges takes the one pass
RANGES = 64         # vertex ranges of about equal rows that the pass is cut into
_memo = {"graph": None, "runs": {}}


def _mark_wide(csr, member, seen, pool):
    """`seen[v] = True` for every row u -> v whose u is a `member`."""
    n = member.size
    cuts = np.searchsorted(csr.indptr, np.linspace(0, csr.nbr.size, RANGES + 1)[1:-1])
    cuts = np.unique(np.concatenate([[0], np.minimum(cuts, n), [n]])).tolist()

    def one(lo, hi):
        rows = np.repeat(member[lo:hi], np.diff(csr.indptr[lo:hi + 1]))
        seen[csr.nbr[csr.indptr[lo]:csr.indptr[hi]][rows]] = True
    list(pool.map(one, cuts[:-1], cuts[1:]))


def levels(ref, over, start, max_steps):
    """-> (level of every vertex as int8, [frontier size, out-edges of
    the frontier] per level expanded, all `max_steps` of them), as
    `bfs_levels.levels`."""
    n = ref.n
    level = np.full(n, -1, np.int8)
    level[start] = 0
    frontier = np.asarray([start], np.int64)
    expanded = []
    with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as pool:
        for depth in range(1, int(max_steps) + 1):
            seen = np.zeros(n, bool)
            edges = 0
            for et in over:
                csr = ref.csr[et]
                out = int((csr.indptr[frontier + 1] - csr.indptr[frontier]).sum())
                edges += out
                if out * WIDE > csr.nbr.size:
                    _mark_wide(csr, level == depth - 1, seen, pool)
                elif out:
                    seen[csr.nbr[_slots(csr, frontier)[0]]] = True
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
