"""Kernels: the least time one chip could take for the causal attention a
step requires (the architecture's counts: compute bound at these shapes)
over the time the splash kernels took."""
from benchmarks.harness import roofline_seconds
from benchmarks.layer_metrics import _attn

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s"
RUNNERS = ("train",)
COUNTS = ("attention_kernel_flops", "attention_kernel_bytes")


def read(ctx):
    if ctx.trace is None:
        return None
    took = _attn.kernel_seconds_per_step(ctx)
    if took is None:
        return None
    c = ctx.counters
    seqs = c["global_batch"] / ctx.chips
    least, _bound = roofline_seconds(
        ctx.counts.attention_kernel_flops(ctx.fields, c["seq_len"], seqs),
        ctx.counts.attention_kernel_bytes(ctx.fields, c["seq_len"], seqs),
        ctx.peaks)
    return 100.0 * least / took
