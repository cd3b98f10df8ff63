"""Scheduler: share of the window's device idle time under no program
span: between rounds, in the load generator's loop."""
from benchmarks.layer_metrics import _scopes

LAYER = "scheduler"
UNIT = "%"
MOVES = "serve_tpot_p50_ms"
RUNNERS = ("serve",)


def read(ctx):
    tab = _scopes.table(ctx)
    if tab is None or not tab.span_counts.get(_scopes.ROUND) \
            or not tab.idlest.idle_ns:
        return None
    return 100.0 * tab.idle_ns(lambda stack: not stack) / tab.idlest.idle_ns
