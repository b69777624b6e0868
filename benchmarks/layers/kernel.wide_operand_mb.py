"""`kernel.wide_operand_mb` — kernels (tpu/hop.py programs as
tpu/runtime.py `_escalate_locked` launches them): MB of 64-bit-typed
leaves among the operands of a traverse program, per program run (series
`tpu_wide_operand_bytes`: sum over count of the window's run).  A chip
without 64-bit lanes splits every such operand WHOLE into its 32-bit
halves at the top of every run, so these are bytes read and written
again for nothing; since property columns are pinned as their halves
(PR 35) it reads 0, and a 64-bit operand that slips into a hop program
shows here before it shows in a trace.  Nothing to read on a program
without the series."""

NEEDS = ("tpu_wide_operand_bytes.count",)


def read(ctx):
    runs = ctx["counter"]("tpu_wide_operand_bytes.count")
    if not runs:
        return None
    return ctx["counter"]("tpu_wide_operand_bytes.sum") / runs / 1e6
