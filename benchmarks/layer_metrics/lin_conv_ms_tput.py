"""Model step: device self time of the decode program's ``lin_conv``
subscope per launch: the linear layers' input projection into the conv's
channels, the depthwise causal conv over them with its bias and SiLU, and
the tail's update, all linear layers of one decode step (the Mamba-2
block: 8,448 channels, nine times a step).  A program that opens no such
scope gives nothing."""
from benchmarks.layer_metrics import _linscopes

LAYER = "model step"
UNIT = "ms"
MOVES = "serve_tokens_per_s"
RUNNERS = ("serve",)


def read(ctx):
    return _linscopes.subscope_ms_per_launch(ctx, ("lin_conv",), "decode")
