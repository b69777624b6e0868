"""`setup_s` — everything before the window, from process start: imports,
data from the seed, build or load, first export and pin, compile, one
replay of the request list.  The plain reference's seconds are left out."""


def read(ctx):
    return ctx["setup_s"]
