"""Host runtime: how long the loop waited in ``next(prefetcher)``, a step."""
LAYER = "host runtime"
UNIT = "ms/step"
MOVES = "train_tokens_per_s"
RUNNERS = ("train",)


def read(ctx):
    waits = ctx.counters["prefetch_wait_s"]
    return 1e3 * sum(waits) / len(waits) if waits else None
