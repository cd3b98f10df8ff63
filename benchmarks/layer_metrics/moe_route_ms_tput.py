"""Model step: device self time of the decode program's ``moe_route``
subscope per launch: the router's product in float32, its scores over the
router's whole width, the top-k, the chosen weights' renormalisation and
the layer's counters, all expert layers of one decode step."""
from benchmarks.layer_metrics import _subscopes

LAYER = "model step"
UNIT = "ms"
MOVES = "serve_tokens_per_s"
RUNNERS = ("serve",)


def read(ctx):
    return _subscopes.subscope_ms_per_launch(ctx, ("moe_route",), "decode")
