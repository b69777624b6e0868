"""`dispatch.retries_per_stmt` — device dispatch: re-dispatches after an
edge-budget overflow (`tpu_escalation_retries`, i.e.
`TraverseStats.retries` summed), per statement."""
from benchmarks.lib.phases import count_per_stmt


def read(ctx):
    return count_per_stmt(ctx, "tpu_escalation_retries")
