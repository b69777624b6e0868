"""Driver `closed_loop_rw`: `closed_loop` for requests whose expected
row count GROWS with the writes the system has acknowledged.  A reply is
`ok` when its row count equals the generator's rows of the source plus
the new edges acknowledged from it so far (`reference/ops/write_read.py`
`rows_now`, the harness's own bookkeeping), read at the moment the reply
is in.  Everything else (sessions, the window, what a record holds) is
`closed_loop`'s."""
from __future__ import annotations

from benchmarks.drivers import closed_loop
from benchmarks.lib.requests import op_module


class _Growing(dict):
    """A request whose `rows` is what the reference expects NOW."""

    def __getitem__(self, key):
        if key == "rows":
            return op_module(dict.__getitem__(self, "template")["op"]).rows_now(self)
        return dict.__getitem__(self, key)


def run(sessions, requests, **kw):
    return closed_loop.run(sessions, [_Growing(r) for r in requests], **kw)
