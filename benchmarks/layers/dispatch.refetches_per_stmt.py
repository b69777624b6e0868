"""`dispatch.refetches_per_stmt` — device dispatch: second fetches after
a speculative fetch that undershot (`tpu_refetches`), per statement."""
from benchmarks.lib.phases import count_per_stmt


def read(ctx):
    return count_per_stmt(ctx, "tpu_refetches")
