"""`xla.compiles_in_window` — XLA compile: jax.monitoring
backend-compile events between the window's start and its close.  The
warm-up replay should leave none."""


def read(ctx):
    return ctx["compiles_in_window"]
