"""What the PR-39 layer readers share.  Since PR 39 a device statement
that enters below graphd is rooted by the runtime's own entry
(`query:tpu.<entry>`, tpu/runtime.py `_on_live_snapshot`), so the phase
ledger (`stmt_phase_us{phase}`, folded when a `query:*` root closes) and
the program's spans in a profiler trace exist in EVERY cell: `phase_ms`
is `lib/phases.py`'s for a cell of either kind, and the two interval
helpers read `ctx["events"]` (lib/trace.py `load`: plain tuples).

A program without the phase label (a parent commit from before it)
keeps no such key in its snapshot: `phase_ms` then returns None and the
line leaves the metric out."""
from __future__ import annotations

from benchmarks.lib import trace as T
from benchmarks.lib.phases import kept, statements

PHASE_US = "stmt_phase_us{{phase={}}}"
PHASE_N = "stmt_phase_n{{phase={}}}"


def phase_ms(ctx, *phases, needs=()):
    """Mean ms a statement of the window's run spent in these phases
    (the self time of their spans): by graphd's own count of statements
    where they pass graphd, else by the statements the driver sent.
    None unless the program keeps each of these phases' labels and each
    of `needs` (the label that tells this PR's split of a phase from the
    whole the parent books under the same name)."""
    n = statements(ctx)
    if not n or not all(kept(PHASE_US.format(p)) for p in phases + tuple(needs)):
        return None
    return sum(ctx["counter"](PHASE_US.format(p)) for p in phases) / 1e3 / n


def clipped(intervals, t0, t1):
    """The union of these [start, end) intervals inside [t0, t1): a
    sorted list of disjoint [start, end]."""
    return T.union((max(s, t0), min(e, t1)) for s, e in intervals if e > t0 and s < t1)


def overlap_ns(a, b):
    """ns that two sorted lists of disjoint intervals share."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def length_ns(a):
    return sum(e - s for s, e in a)


def slice_of(ctx):
    """-> (t0, t1, the ns in which some device plane runs an operation,
    the program's spans) of the traced slice, or None without one."""
    events = ctx["events"]
    bounds = T.window(events) if events else None
    if bounds is None:
        return None
    t0, t1 = bounds
    busy = clipped(((s, e) for ops in events["devices"].values() for _, s, e in ops), t0, t1)
    return t0, t1, busy, events["spans"]
