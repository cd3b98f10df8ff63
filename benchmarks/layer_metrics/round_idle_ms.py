"""Scheduler: device idle time inside the engine's ``serve/round`` spans
per round: what the host's round costs the chip."""
from benchmarks.layer_metrics import _scopes

LAYER = "scheduler"
UNIT = "ms"
MOVES = "serve_tpot_p50_ms"
RUNNERS = ("serve",)


def read(ctx):
    return _scopes.round_idle_ms(ctx)
