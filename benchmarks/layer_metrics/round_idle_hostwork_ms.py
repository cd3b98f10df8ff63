"""Scheduler: the part of ``round_idle_ms`` under the host's own work:
``serve/bookkeep``, ``serve/admit``, ``*_stage``, Python between launches.
``_crossings`` splits ``round_idle_ms`` four ways; the parts sum to it."""
from benchmarks.layer_metrics import _crossings

LAYER = "scheduler"
UNIT = "ms"
MOVES = "serve_tpot_p50_ms"
RUNNERS = ("serve",)


def read(ctx):
    return _crossings.round_part_ms(ctx, "hostwork")
