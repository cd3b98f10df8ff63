"""Kernels: device self time of the decode program's ``attn_paged``
subscope per launch: the paged decode attention of the full-attention
layers alone (the kernel's calls and the transposes XLA puts round them),
all such layers of one decode step.  ``decode_attn_ms_tput`` lumps it with
the linear layers' step: both run under ``attn_core``."""
from benchmarks.layer_metrics import _attnscopes

LAYER = "kernels"
UNIT = "ms"
MOVES = "serve_tokens_per_s"
RUNNERS = ("serve",)


def read(ctx):
    return _attnscopes.subscope_ms_per_launch(ctx, ("attn_paged",), "decode")
