"""Strategy / collectives: time a step in which an all-gather is in flight and no
compute op runs on that chip (with the other kinds' it is
``collective_exposed_pct`` of the step); the worst chip."""
from benchmarks.layer_metrics import _collectives

LAYER = "strategy / collectives"
UNIT = "ms/step"
MOVES = "train_tokens_per_s"
RUNNERS = ("train",)


def read(ctx):
    return _collectives.exposed_ms_per_step(ctx, "all_gather")
