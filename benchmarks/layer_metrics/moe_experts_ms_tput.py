"""Model step: device self time of the decode program's ``moe_experts``
subscope per launch: the held experts' grouped product over the (row, held
expert) pairs the routing made and its combine, all expert layers of one
decode step."""
from benchmarks.layer_metrics import _subscopes

LAYER = "model step"
UNIT = "ms"
MOVES = "serve_tokens_per_s"
RUNNERS = ("serve",)


def read(ctx):
    return _subscopes.subscope_ms_per_launch(ctx, ("moe_experts",), "decode")
