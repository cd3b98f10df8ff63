"""Model step: traced device time of the decode program per launch (one
launch is one decode step of the whole batch)."""
from benchmarks.layer_metrics import _programs

LAYER = "model step"
UNIT = "ms"
MOVES = "serve_tpot_p50_ms"
RUNNERS = ("serve",)


def read(ctx):
    s = _programs.device_seconds_per_launch(ctx, "decode")
    return None if s is None else 1e3 * s
