"""`graphd.host_ms` — graphd: parse, plan, schedule, executors, row
assembly (query/, exec/): graphd's mean statement time minus the device
dispatch's mean (d query_latency_us / n - d tpu_kernel_s / n).  Served
cells only."""


def read(ctx):
    n = ctx["counter"]("num_queries")
    if not ctx["served"] or not n:
        return None
    return (ctx["counter"]("query_latency_us.sum") / 1e3
            - ctx["counter"]("tpu_kernel_s.sum") * 1e3) / n
