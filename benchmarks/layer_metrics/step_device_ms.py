"""Model step: device-busy time per step (union of op intervals, averaged
over the chips), from the trace."""
LAYER = "model step"
UNIT = "ms/step"
MOVES = "train_tokens_per_s"
RUNNERS = ("train",)


def read(ctx):
    if ctx.trace is None:
        return None
    return 1e3 * ctx.trace.busy_s / ctx.counters["steps"]
