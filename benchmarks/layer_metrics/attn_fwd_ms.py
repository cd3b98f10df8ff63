"""Kernels: device time of the splash-attention FORWARD Mosaic calls
(``splash_mha_fwd*``) per step, averaged over the chips.  A layer's
forward runs once a step where the kernel's output and log-sum-exp
survive the remat boundary, and twice where the layer's recomputation
re-runs it: the number halves between the two.  A trace without such an
event (an older stack names a Pallas call ``custom-call``) gives nothing."""
LAYER = "kernels"
UNIT = "ms/step"
MOVES = "train_tokens_per_s"
RUNNERS = ("train",)

FORWARD = r"splash_mha_fwd"


def read(ctx):
    if ctx.trace is None:
        return None
    ns = ctx.trace.group_ns(FORWARD)
    return ns / 1e6 / ctx.counters["steps"] if ns else None
