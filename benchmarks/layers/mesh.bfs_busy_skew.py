"""`mesh.bfs_busy_skew` — mesh exchange: the busiest chip's busy seconds
over the chips' mean, inside the traced slice of the sharded BFS cell
(`mesh.busy_skew`'s reading, by import: busy = the union of a chip's
`XLA Ops` intervals).  1.0 is even.  Every chip waits at each level's
exchange for the fullest part's trips, and a collective that waits counts
as busy on its plane: read it beside `mesh.bfs_exchange_ms`, which is
where a skew of the parts shows first."""
from benchmarks.lib import loader


def read(ctx):
    return loader.module("layers", "mesh.busy_skew").read(ctx)
