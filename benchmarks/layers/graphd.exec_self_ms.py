"""`graphd.exec_self_ms` — graphd: the executors' own Python and row
assembly, i.e. the self time of `exec:*` (and `tpu:*`) spans once RPC
waits, the snapshot check and the device phases under them are taken
out, per statement."""
from benchmarks.lib.phases import phase_ms


def read(ctx):
    return phase_ms(ctx, "exec")
