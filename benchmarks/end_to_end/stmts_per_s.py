"""`stmts_per_s` — statements completed in the window with the right row
count, per second of window (the window closes at the first completion at
or after --seconds, and this divides by the seconds that elapsed)."""


def read(ctx):
    return sum(r.ok for r in ctx["window"]) / ctx["elapsed_s"] if ctx["elapsed_s"] > 0 else None
