"""`graphd.plan_cache_hit_share` — graphd: plan-cache hits over lookups
(`plan_cache_hits`, `plan_cache_misses`; exec/engine.py `PlanCache`),
over the window's run."""


def read(ctx):
    hits, misses = ctx["counter"]("plan_cache_hits"), ctx["counter"]("plan_cache_misses")
    if not ctx["served"] or not hits + misses:
        return None
    return 100.0 * hits / (hits + misses)
