"""`host.cpu_cores_busy` — host process: CPU seconds the process used
(`process_cpu_s` = time.process_time(), all threads) over the window's
seconds.  About 1.0: one GIL is saturated; well under 1.0: the process
waits."""
from benchmarks.lib.phases import kept


def read(ctx):
    if not ctx["elapsed_s"] or not kept("process_cpu_s"):
        return None
    return ctx["counter"]("process_cpu_s") / ctx["elapsed_s"]
