"""Kernels: device self time of the decode program's ``kv_write``,
``kv_gather`` and ``attn_core`` scopes per launch: what the paged KV costs
a decode step, apart from the weight reads."""
from benchmarks.layer_metrics import _scopes

LAYER = "kernels"
UNIT = "ms"
MOVES = "serve_tpot_p50_ms"
RUNNERS = ("serve",)
SCOPES = ("kv_write", "kv_gather", "attn_core")


def read(ctx):
    return _scopes.scope_ms_per_launch(ctx, SCOPES, "decode")
