"""`dispatch.fetch_ms` — device dispatch: device-to-host fetch of the
converged run's counters and captured columns, a refetch after an
undershot speculative fetch included (series `tpu_fetch_s`), per
statement."""
from benchmarks.lib.phases import series_ms


def read(ctx):
    return series_ms(ctx, "tpu_fetch_s")
