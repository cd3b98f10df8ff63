"""Scheduler: the part of ``round_idle_ms`` from the start of the
``serve/launch_dispatch`` whose program ended the gap to that program's
first op: the host has called it and the chip has not begun it.
``_crossings`` splits ``round_idle_ms`` four ways; the parts sum to it."""
from benchmarks.layer_metrics import _crossings

LAYER = "scheduler"
UNIT = "ms"
MOVES = "serve_tpot_p50_ms"
RUNNERS = ("serve",)


def read(ctx):
    return _crossings.round_part_ms(ctx, "launch_latency")
