"""Scheduler: the part of ``round_idle_ms`` from the moment the chip fell
idle to the end of the host wait (``pump/*`` or ``serve/*_sync``) that was
already open: the chip is done and the host has not been released.
``_crossings`` splits ``round_idle_ms`` four ways; the parts sum to it."""
from benchmarks.layer_metrics import _crossings

LAYER = "scheduler"
UNIT = "ms"
MOVES = "serve_tpot_p50_ms"
RUNNERS = ("serve",)


def read(ctx):
    return _crossings.round_part_ms(ctx, "wake")
