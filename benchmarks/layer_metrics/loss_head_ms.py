"""Model step: device self time under the program's ``loss_head`` scope
(final norm, streamed cross-entropy and their backward) per step."""
from benchmarks.layer_metrics import _scopes

LAYER = "model step"
UNIT = "ms/step"
MOVES = "train_tokens_per_s"
RUNNERS = ("train",)


def read(ctx):
    return _scopes.scope_ms_per_step(ctx, ("loss_head",))
