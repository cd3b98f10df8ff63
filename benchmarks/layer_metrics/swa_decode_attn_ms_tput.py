"""Kernels: device self time of the decode program's ``attn_window``
subscope per launch: the paged decode attention of the WINDOW layers alone
(the kernel's calls over the rings' ordered views and the transposes XLA
puts round them), all such layers of one decode step.
``decode_attn_ms_tput`` lumps it with the full layer's and with
``kv_write``."""
from benchmarks.layer_metrics import _winscopes

LAYER = "kernels"
UNIT = "ms"
MOVES = "serve_tokens_per_s"
RUNNERS = ("serve",)


def read(ctx):
    return _winscopes.subscope_ms_per_launch(ctx, ("attn_window",), "decode")
