"""`kernel.expand_gathers` — kernels (tpu/hop.py `_expand_slots` as
`_traverse` runs it by need): gathers with one index a slot that the
expansion stage of a traverse program's LAST hop issues, per program run
(series `tpu_hop_slot_gathers`, observed once a converged launch by
tpu/runtime.py `_escalate_locked`: sum over count of the window's run).
A slot of the widest hop costs what its gathers cost (14 to 20 ns an
index on the chip), so this is the count the expansion loop's time
follows at a fixed number of trips: `nbr`, the row-offset table, the
compact-row table on a whole-bitmap plan, the hub ids of a degree split,
a predicate's columns, and an edge's rank only where the statement, its
predicate, a MATCH frame or an armed delta plane reads it (PR 38).  The
proxy cells' three-hop GOs read 2.  Nothing to read on a program without
the series."""

NEEDS = ("tpu_hop_slot_gathers.count",)


def read(ctx):
    runs = ctx["counter"]("tpu_hop_slot_gathers.count")
    if not runs:
        return None
    return ctx["counter"]("tpu_hop_slot_gathers.sum") / runs
