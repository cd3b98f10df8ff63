"""Kernels: device self time of the prefill program's ``attn_window``
subscope per launch: the flash prefill attention of the WINDOW layers
alone, all such layers of one chunk."""
from benchmarks.layer_metrics import _winscopes

LAYER = "kernels"
UNIT = "ms"
MOVES = "serve_tokens_per_s"
RUNNERS = ("serve",)


def read(ctx):
    return _winscopes.subscope_ms_per_launch(ctx, ("attn_window",),
                                             "prefill")
