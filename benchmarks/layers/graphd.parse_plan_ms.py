"""`graphd.parse_plan_ms` — graphd: tokenise and parse (`graphd:parse`,
exec/engine.py `_execute`) plus validate, plan and optimise (`graphd:plan`,
`_execute_inner`; absent on a plan-cache hit), per statement."""
from benchmarks.lib.phases import phase_ms


def read(ctx):
    return phase_ms(ctx, "parse", "plan")
