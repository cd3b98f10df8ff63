"""Kernels: device self time of the prefill program's ``kv_write``,
``kv_gather`` and ``attn_core`` scopes per launch (one chunk)."""
from benchmarks.layer_metrics import _scopes
from benchmarks.layer_metrics.decode_attn_ms import SCOPES

LAYER = "kernels"
UNIT = "ms"
MOVES = "serve_tokens_per_s"
RUNNERS = ("serve",)


def read(ctx):
    return _scopes.scope_ms_per_launch(ctx, SCOPES, "prefill")
