"""Strategy / collectives: the all-gathers' bus bandwidth, message times (n - 1)/n
over in-flight time (an async gather's start to its done, so one paced
under the compute it hides behind reads the pace); the slowest chip.
``peaks.json`` gives a chip 200 GB/s over all its ICI links."""
from benchmarks.layer_metrics import _collectives

LAYER = "strategy / collectives"
UNIT = "GB/s"
MOVES = "train_tokens_per_s"
RUNNERS = ("train",)


def read(ctx):
    return _collectives.busbw_gbps(ctx, "all_gather")
