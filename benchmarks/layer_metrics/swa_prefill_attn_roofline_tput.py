"""Kernels: the least time one chip could take for the attention the WINDOW
layers require of a prefill chunk (the architecture's counts at the engine's
``window_pairs_prefilled / prefill_chunks``: the (row, key) pairs the band
holds for a chunk's valid rows, 4 x head_dim FLOPs a pair and query head)
over ``swa_prefill_attn_ms_tput``.  Compute bound.  The kernel multiplies
whole blocks of 128 keys by all of a chunk's rows and masks; the counts hold
the band's pairs alone, so the share cannot pass 100."""
from benchmarks import harness
from benchmarks.layer_metrics import swa_prefill_attn_ms_tput

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_tokens_per_s"
RUNNERS = ("serve",)
COUNTS = ("window_prefill_attention_flops", "window_prefill_attention_bytes")


def read(ctx):
    s = ctx.counters["stats"]
    took_ms = swa_prefill_attn_ms_tput.read(ctx)
    if took_ms is None or not s.get("window_pairs_prefilled") \
            or not s.get("prefill_chunks"):
        return None
    pairs = s["window_pairs_prefilled"] / s["prefill_chunks"]
    rows = float(ctx.counters["engine"]["prefill_chunk"])
    least, _ = harness.roofline_seconds(
        ctx.counts.window_prefill_attention_flops(ctx.fields, pairs),
        ctx.counts.window_prefill_attention_bytes(ctx.fields, pairs, rows),
        ctx.peaks)
    return 100.0 * least / (took_ms / 1e3)
