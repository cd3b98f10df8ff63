"""Kernels: device time of the splash-attention Mosaic calls (forward, dq,
dkv: the forward runs once a layer, its output and log-sum-exp are kept
across the remat boundary) per step, averaged over the chips."""
from benchmarks.layer_metrics import _attn

LAYER = "kernels"
UNIT = "ms/step"
MOVES = "train_tokens_per_s"
RUNNERS = ("train",)


def read(ctx):
    if ctx.trace is None:
        return None
    s = _attn.kernel_seconds_per_step(ctx)
    return None if s is None else 1e3 * s
