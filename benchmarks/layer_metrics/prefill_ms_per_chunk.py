"""Model step: traced device time of the prefill program per launch (one
launch is one chunk of ``prefill_chunk`` prompt tokens of one request)."""
from benchmarks.layer_metrics import _programs

LAYER = "model step"
UNIT = "ms"
MOVES = "serve_tpot_p50_ms"
RUNNERS = ("serve",)


def read(ctx):
    s = _programs.device_seconds_per_launch(ctx, "prefill")
    return None if s is None else 1e3 * s
