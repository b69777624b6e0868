"""`kernel.plan_share` — kernels (tpu/hop.py `_expand_plan`): of the
scatter updates that expansion plans over every local vertex issue (two
a vertex, hop, block and part), the share the hops' plans issued once
laid out from the frontier's members (`tpu_hop_plan_run` /
`tpu_hop_plan_budget`, i.e. `TraverseStats.plan_run / plan_budget`
summed), over the window's run.  Both counters stay where every bitmap
is narrow enough for the whole-bitmap plan, and a program that lacks
them has none: nothing to read there.

Read it beside `dispatch.device_ms`: it says how much of the plan's old
work is still issued, not what the rest of a hop costs."""

# the counters without whose movement there is nothing to read: a tier-1
# rehearsal may leave the metric out only where none of them moved
NEEDS = ("tpu_hop_plan_budget",)


def read(ctx):
    budget = ctx["counter"]("tpu_hop_plan_budget")
    if not budget:
        return None
    return 100.0 * ctx["counter"]("tpu_hop_plan_run") / budget
