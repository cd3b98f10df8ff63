"""Strategy / collectives: the reduce-scatters' bus bandwidth, message times
(n - 1)/n over in-flight time; the slowest chip.  ``peaks.json`` gives a chip
200 GB/s over all its ICI links."""
from benchmarks.layer_metrics import _collectives

LAYER = "strategy / collectives"
UNIT = "GB/s"
MOVES = "train_tokens_per_s"
RUNNERS = ("train",)


def read(ctx):
    return _collectives.busbw_gbps(ctx, "reduce_scatter")
