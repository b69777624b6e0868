"""`graphd.rpcs_per_stmt` — graphd: internal RPCs a statement makes:
`rpc:*` spans under its root (`stmt_phase_n{phase=rpc_wait}`; a
`storage:*` span wraps one and is not counted again)."""
from benchmarks.lib.phases import phase_spans


def read(ctx):
    return phase_spans(ctx, "rpc_wait")
