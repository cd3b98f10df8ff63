"""Scheduler: the part of ``round_idle_ms`` under ``serve/*_sync`` and
``serve/bookkeep``: the chip waits while the host reads results back and
updates its state."""
from benchmarks.layer_metrics import _scopes

LAYER = "scheduler"
UNIT = "ms"
MOVES = "serve_tpot_p50_ms"
RUNNERS = ("serve",)


def read(ctx):
    return _scopes.round_idle_ms(ctx, _scopes.READBACK.match)
