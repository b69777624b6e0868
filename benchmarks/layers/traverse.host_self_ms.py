"""`traverse.host_self_ms` — device dispatch: the runtime's own host work
around a launch (`stmt_phase_us{phase=exec}` in a cell that enters at
`TpuRuntime.traverse`: the self time of `tpu:prep` (pin, predicate, dense
ids), `tpu:launch` (block leaves, key closures, the gate's and a rung's
bookkeeping, the charge to the statement), `tpu:seed_prep`,
`tpu:launch_account` and `tpu:fetch_warm`), per statement the driver
sent.  Nothing on a program that opens no root there (the parent)."""
from benchmarks.lib.spans import PHASE_N, phase_ms

NEEDS = (PHASE_N.format("exec"),)


def read(ctx):
    return phase_ms(ctx, "exec")
