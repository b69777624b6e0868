"""`mesh.launch_wait_ms` — device dispatch: what a statement's launches
(its seed put and each kernel run) waited for the collective-launch mutex
(series `tpu_collective_wait_s`, tpu/runtime.py `_collective_launch`,
beside `tpu_queue_s`), per statement.  With one session nothing waits;
with two, one statement's put and dispatch queue behind the other's
kernel: ROADMAP S8's cost on the clock.  The series is not kept in local
mode, where no launch takes the mutex."""
from benchmarks.lib.phases import series_ms

NEEDS = ("tpu_collective_wait_s.count",)


def read(ctx):
    return series_ms(ctx, "tpu_collective_wait_s")
