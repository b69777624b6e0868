"""`graphd.rpc_wait_ms` — graphd: what a statement waits for internal
RPCs: the snapshot freshness probe (`tpu:snapshot_check`, tpu/runtime.py
`pin`), the self time of `storage:*` / `rpc:*` spans (transport and
queueing) and the handlers themselves (`remote`), per statement."""
from benchmarks.lib.phases import phase_ms


def read(ctx):
    return phase_ms(ctx, "snapshot_check", "rpc_wait", "remote")
