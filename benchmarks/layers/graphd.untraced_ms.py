"""`graphd.untraced_ms` — graphd: the self time of a statement's root
span (`stmt_phase_us{phase=other}`): what no child span explains, from
entry to return of `GraphService.rpc_execute`."""
from benchmarks.lib.phases import phase_ms


def read(ctx):
    return phase_ms(ctx, "other")
