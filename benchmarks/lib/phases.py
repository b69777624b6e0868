"""What the PR-24 layer readers share: per-statement means of the
program's statement phase ledger (`stmt_phase_us{phase}` /
`stmt_phase_n{phase}`, folded from the span tree in utils/trace.py when a
statement's root closes) and of its process-wide device series
(`tpu_put_s`, `tpu_fetch_s`, ...), as differences over the window's run.

A program that keeps no such counter (a parent commit from before them)
has no such key in its snapshot: every reader here then returns None, and
the line leaves the metric out."""
from __future__ import annotations

from nebula_tpu.utils.stats import stats


def statements(ctx) -> int:
    """What the window's run divides by: graphd's own count where
    statements pass graphd, else the statements the driver sent."""
    return ctx["counter"]("num_queries") if ctx["served"] else len(ctx["records"])


def kept(prefix: str) -> bool:
    return any(k.startswith(prefix) for k in stats().snapshot())


def phase_ms(ctx, *phases):
    """Mean ms of a statement's root that these phases' spans account
    for (self time).  Served cells only: the proxy cell enters below the
    statement and opens no trace."""
    n = ctx["counter"]("num_queries")
    if not ctx["served"] or not n or not kept("stmt_phase_us{"):
        return None
    return sum(ctx["counter"](f"stmt_phase_us{{phase={p}}}") for p in phases) / 1e3 / n


def phase_spans(ctx, phase):
    """Mean number of the phase's spans in a statement."""
    n = ctx["counter"]("num_queries")
    if not ctx["served"] or not n or not kept("stmt_phase_n{"):
        return None
    return ctx["counter"](f"stmt_phase_n{{phase={phase}}}") / n


def series_ms(ctx, name, scale=1e3):
    """Mean per statement of a series' sum, in ms (`scale` from the
    series' unit: 1e3 for seconds, 1e-3 for microseconds)."""
    n = statements(ctx)
    if not n or not kept(name + ".sum"):
        return None
    return ctx["counter"](name + ".sum") * scale / n


def count_per_stmt(ctx, name):
    n = statements(ctx)
    if not n or not kept(name):
        return None
    return ctx["counter"](name) / n
