"""`graphd.encode_ms` — graphd: `to_wire` of the result (`graphd:encode`,
cluster/graph_service.py `_execute`), per statement."""
from benchmarks.lib.phases import phase_ms


def read(ctx):
    return phase_ms(ctx, "encode")
