"""Device: 1 - union of device-op intervals over the traced window, on the
chip that idles most."""
LAYER = "device"
UNIT = "%"
MOVES = "train_tokens_per_s"
RUNNERS = ("train",)


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace.worst("idle_share")
