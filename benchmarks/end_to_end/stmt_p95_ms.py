"""`stmt_p95_ms` — 95th percentile of the caller's statement latency,
over every statement completed in the window (only in cells whose window
holds some hundreds: ten samples beyond it need two hundred)."""
from benchmarks.lib.arith import percentile


def read(ctx):
    return 1e3 * percentile([r.latency_s() for r in ctx["window"]], 95) if ctx["window"] else None
