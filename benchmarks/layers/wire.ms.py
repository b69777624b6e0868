"""`wire.ms` — client and wire (cluster/client.py, core/wire.py,
cluster/rpc.py): mean caller latency minus graphd's own mean statement
time (d query_latency_us / d num_queries), over every statement the run
sent.  With several sessions both include waiting.  Served cells only."""


def read(ctx):
    n = ctx["counter"]("num_queries")
    if not ctx["served"] or not n or not ctx["records"]:
        return None
    caller_ms = 1e3 * sum(r.latency_s() for r in ctx["records"]) / len(ctx["records"])
    return caller_ms - ctx["counter"]("query_latency_us.sum") / n / 1e3
