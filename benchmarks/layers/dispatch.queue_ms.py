"""`dispatch.queue_ms` — device dispatch: wait at the dispatch gate (and
in the batch former, when it is on) before a kernel may launch (series
`tpu_queue_s`, tpu/runtime.py, beside `tpu_kernel_s`), per statement."""
from benchmarks.lib.phases import series_ms


def read(ctx):
    return series_ms(ctx, "tpu_queue_s")
