"""Model step: device self time of the ops the program scoped ``attn_qkv``,
``attn_out`` and ``mlp`` (norms, projections, RoPE, SwiGLU; forward, remat
re-run and backward) per step, mean over the chips."""
from benchmarks.layer_metrics import _scopes

LAYER = "model step"
UNIT = "ms/step"
MOVES = "train_tokens_per_s"
RUNNERS = ("train",)
SCOPES = ("attn_qkv", "attn_out", "mlp")


def read(ctx):
    return _scopes.scope_ms_per_step(ctx, SCOPES)
