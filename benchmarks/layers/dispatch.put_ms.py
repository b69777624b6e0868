"""`dispatch.put_ms` — device dispatch: the seed frontier's transfer to
the device (series `tpu_put_s`, tpu/runtime.py `_escalate_locked`), per
statement."""
from benchmarks.lib.phases import series_ms


def read(ctx):
    return series_ms(ctx, "tpu_put_s")
