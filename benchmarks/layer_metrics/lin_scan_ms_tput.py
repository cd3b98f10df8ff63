"""Kernels: device self time of the prefill program's ``lin_scan`` subscope
per launch: the chunked scan of one prefill chunk from the slot's carried
state, all linear layers."""
from benchmarks.layer_metrics import _linscopes

LAYER = "kernels"
UNIT = "ms"
MOVES = "serve_tokens_per_s"
RUNNERS = ("serve",)


def read(ctx):
    return _linscopes.subscope_ms_per_launch(ctx, ("lin_scan",), "prefill")
