"""Kernels: device self time of the decode program's ``lin_step`` subscope
per launch: the gated delta rule's recurrence on every slot's state, all
linear layers of one decode step."""
from benchmarks.layer_metrics import _linscopes

LAYER = "kernels"
UNIT = "ms"
MOVES = "serve_tokens_per_s"
RUNNERS = ("serve",)


def read(ctx):
    return _linscopes.subscope_ms_per_launch(ctx, ("lin_step",), "decode")
