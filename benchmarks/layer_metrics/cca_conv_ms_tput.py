"""Model step: device self time of the decode program's ``cca_conv``
subscope per launch: the two causal convolutions over the latents continued
from every slot's tail, the q-k mean, the value shift and the tail's update,
all layers of one decode step (compressed convolutional attention: 1,280
channels in 10 groups of 128, sixteen times a step).  A chain of small ops,
so it says what their launches cost.  A program that opens no such scope
gives nothing."""
from benchmarks.layer_metrics import _ccascopes

LAYER = "model step"
UNIT = "ms"
MOVES = "serve_tokens_per_s"
RUNNERS = ("serve",)


def read(ctx):
    return _ccascopes.subscope_ms_per_launch(ctx, ("cca_conv",), "decode")
