"""Scheduler: the part of ``round_idle_ms`` under ``serve/admit``,
``serve/*_stage`` and ``serve/*_dispatch``: the chip waits while the host
prepares and launches work."""
from benchmarks.layer_metrics import _scopes

LAYER = "scheduler"
UNIT = "ms"
MOVES = "serve_tpot_p50_ms"
RUNNERS = ("serve",)


def read(ctx):
    return _scopes.round_idle_ms(ctx, _scopes.LAUNCH.match)
