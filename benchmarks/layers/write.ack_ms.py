"""`write.ack_ms` — storage write path (cluster/dstore.py `_write_many`):
a mutating statement from graphd's entry to storaged's acknowledgement,
every part's reply in, through raft and the WAL (series `write_ack_s`),
per write request, over the window's run."""

NEEDS = ("write_ack_s.count",)


def read(ctx):
    writes = ctx["counter"]("write_ack_s.count")
    if not writes:
        return None
    return ctx["counter"]("write_ack_s.sum") * 1e3 / writes
