"""Scheduler: host-to-device puts a round, one an array: ``h2d_puts / rounds``
of the engine's own counters (a burst ships five mirrors, a prefill chunk
four or five arrays).
A program without the counter gives nothing."""
from benchmarks.layer_metrics import _crossings

LAYER = "scheduler"
UNIT = "puts"
MOVES = "serve_tpot_p50_ms"
RUNNERS = ("serve",)


def read(ctx):
    return _crossings.per_round(ctx, "h2d_puts")
