"""Device: 1 - union of device-op intervals over the traced window."""
LAYER = "device"
UNIT = "%"
MOVES = "serve_tpot_p50_ms"
RUNNERS = ("serve",)


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace.worst("idle_share")
