"""Model step: device self time under the strategy's ``opt_step`` scope
(the AdamW update of the chip's shards) per step."""
from benchmarks.layer_metrics import _scopes

LAYER = "model step"
UNIT = "ms/step"
MOVES = "train_tokens_per_s"
RUNNERS = ("train",)


def read(ctx):
    return _scopes.scope_ms_per_step(ctx, ("opt_step",))
