"""Scheduler: blocking device-to-host reads a round, one an array:
``d2h_reads / rounds`` of the engine's own counters (a burst's sync point is
``sync_every`` reads and one for the device's counters, a finished prompt
one).
A program without the counter gives nothing."""
from benchmarks.layer_metrics import _crossings

LAYER = "scheduler"
UNIT = "reads"
MOVES = "serve_tpot_p50_ms"
RUNNERS = ("serve",)


def read(ctx):
    return _crossings.per_round(ctx, "d2h_reads")
