"""`stmt_p50_ms` — median statement latency on the caller's clock (send
to last row received), over every statement completed in the window."""
from benchmarks.lib.arith import percentile


def read(ctx):
    return 1e3 * percentile([r.latency_s() for r in ctx["window"]], 50) if ctx["window"] else None
