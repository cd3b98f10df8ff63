"""Model step: the least time one chip could take for the matmuls of the
projection and MLP weights a step requires (6 x their parameters x the
chip's tokens, the architecture's counts; recomputation not counted,
compute bound) over ``proj_mlp_ms``, which also holds the norms, RoPE and
the remat re-run: the share cannot pass 100."""
from benchmarks.layer_metrics import proj_mlp_ms

LAYER = "model step"
UNIT = "%"
MOVES = "train_tokens_per_s"
RUNNERS = ("train",)
COUNTS = ("proj_mlp_weight_count",)


def read(ctx):
    took_ms = proj_mlp_ms.read(ctx)
    if took_ms is None:
        return None
    c = ctx.counters
    need = 6.0 * ctx.counts.proj_mlp_weight_count(ctx.fields) \
        * c["tokens"] / c["steps"] / ctx.chips
    return 100.0 * need / ctx.peaks["bf16_flops_per_s"] / (took_ms / 1e3)
