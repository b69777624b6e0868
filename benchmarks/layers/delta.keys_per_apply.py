"""`delta.keys_per_apply` — delta plane: dirty keys one delta apply
folded in (series `tpu_delta_keys`, one observation a successful apply),
over the window's run.  One session that reads after every write reads 1;
more means writes piled up between reads (or keys noted twice)."""

NEEDS = ("tpu_delta_keys.count",)


def read(ctx):
    applies = ctx["counter"]("tpu_delta_keys.count")
    if not applies:
        return None
    return ctx["counter"]("tpu_delta_keys.sum") / applies
