"""Strategy / collectives: share of the traced window in which a collective
is in flight and no compute op runs on that chip; the worst chip."""
LAYER = "strategy / collectives"
UNIT = "%"
MOVES = "train_tokens_per_s"
RUNNERS = ("train",)


def read(ctx):
    if ctx.trace is None or ctx.chips < 2:
        return None
    return 100.0 * max(c.collective_exposed_ns / c.window_ns
                       for c in ctx.trace.chips)
