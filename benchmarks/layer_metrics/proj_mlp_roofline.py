"""Model step: the least time one chip could take for the matmuls of the
projection and MLP weights a step requires (6 x their parameters x the
chip's tokens, ``benchmarks/flops.py``'s terms; recomputation not counted,
compute bound) over ``proj_mlp_ms``, which also holds the norms, RoPE and
the remat re-run: the share cannot pass 100."""
from benchmarks import flops
from benchmarks.layer_metrics import proj_mlp_ms

LAYER = "model step"
UNIT = "%"
MOVES = "train_tokens_per_s"
RUNNERS = ("train",)


def weight_count(fields: dict) -> int:
    """Parameters of q, k, v, o and the three MLP matrices, every layer."""
    h, nq, nkv, hd = flops._dims(fields)
    per_layer = h * hd * (2 * nq + 2 * nkv) \
        + 3 * h * int(fields["intermediate_size"])
    return int(fields["num_hidden_layers"]) * per_layer


def read(ctx):
    took_ms = proj_mlp_ms.read(ctx)
    if took_ms is None:
        return None
    c = ctx.counters
    need = 6.0 * weight_count(ctx.fields) * c["tokens"] / c["steps"] \
        / ctx.chips
    return 100.0 * need / ctx.peaks["bf16_flops_per_s"] / (took_ms / 1e3)
