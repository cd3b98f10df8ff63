"""Model step: FLOPs the forward and backward passes REQUIRE for the
traced steps' tokens (the architecture's counts; recomputation not counted)
over traced time x chips x the bf16 peak."""

LAYER = "model step"
UNIT = "%"
MOVES = "train_tokens_per_s"
RUNNERS = ("train",)
COUNTS = ("model_flops_per_token",)


def read(ctx):
    c = ctx.counters
    need = ctx.counts.model_flops_per_token(ctx.fields, c["seq_len"]) \
        * c["tokens"]
    have = c["elapsed_s"] * ctx.chips * ctx.peaks["bf16_flops_per_s"]
    return 100.0 * need / have
