"""Device: share of the busy self time whose op carries no name of the
program's scope catalogue: the tracing's own coverage."""
from benchmarks.layer_metrics import _scopes

LAYER = "device"
UNIT = "%"
MOVES = "train_tokens_per_s"
RUNNERS = ("train",)


def read(ctx):
    tab = _scopes.table(ctx)
    if tab is None or not tab.busy_self_ns():
        return None
    return 100.0 * tab.scope_ns((_scopes.NO_SCOPE,)) / tab.busy_self_ns()
