"""`kernel.bfs_budget_fill` — kernels (tpu/bfs.py over algo/frontier.py):
of the slots the BFS level bodies ran (`tpu_bfs_budget_slots`: parts x the
converged per-level edge budgets, which a level body runs whole), the
share that held an edge (`tpu_bfs_edges`: the slots a level really
expanded, in-edges of the unvisited for a bottom-up level), over the
window's run.  What chunking a level by need would raise.  Nothing to read
on a program without the counters."""

NEEDS = ("tpu_bfs_budget_slots",)


def read(ctx):
    slots = ctx["counter"]("tpu_bfs_budget_slots")
    if not slots:
        return None
    return 100.0 * ctx["counter"]("tpu_bfs_edges") / slots
