"""`kernel.bfs_bottom_up_levels` — kernels (tpu/bfs.py): levels a BFS
statement took bottom-up (`tpu_bfs_levels_bottom_up` / `tpu_bfs_runs`),
over the window's run.  0 means the direction switch never fired: the
branch the paths cell is there to work is not taken.  Nothing to read on
a program without the counters."""

NEEDS = ("tpu_bfs_runs",)


def read(ctx):
    runs = ctx["counter"]("tpu_bfs_runs")
    if not runs:
        return None
    return ctx["counter"]("tpu_bfs_levels_bottom_up") / runs
