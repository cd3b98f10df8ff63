"""Scheduler: the part of ``round_idle_ms`` under ``serve/*_sync`` spans
that opened after the chip fell idle: a sync point's further blocking
reads, one an array.  ``_crossings`` splits ``round_idle_ms`` four ways; the
parts sum to it."""
from benchmarks.layer_metrics import _crossings

LAYER = "scheduler"
UNIT = "ms"
MOVES = "serve_tpot_p50_ms"
RUNNERS = ("serve",)


def read(ctx):
    return _crossings.round_part_ms(ctx, "read")
