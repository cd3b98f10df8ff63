"""Kernels: the least time one chip could take for the decode attention
the full-attention layers require in a step (the architecture's counts:
per such layer, query head and live cached position 4 x head_dim FLOPs and
the position's K and V rows' bytes once, plus the slots' queries in and
outputs out; live positions from the runner's ``kv_valid_sum /
kv_samples``, slots from the engine's occupancy) over
``paged_attn_ms_tput``.  Memory bound while a KV head serves few query
rows; the counts decide."""
from benchmarks import harness
from benchmarks.layer_metrics import paged_attn_ms_tput

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_tokens_per_s"
RUNNERS = ("serve",)
COUNTS = ("paged_decode_attention_flops", "paged_decode_attention_bytes")


def read(ctx):
    c = ctx.counters
    s = c["stats"]
    took_ms = paged_attn_ms_tput.read(ctx)
    if took_ms is None or not c.get("kv_samples") or not s.get("rounds"):
        return None
    live = c["kv_valid_sum"] / c["kv_samples"]
    slots = s["occupancy_sum"] / s["rounds"]
    least, _ = harness.roofline_seconds(
        ctx.counts.paged_decode_attention_flops(ctx.fields, live),
        ctx.counts.paged_decode_attention_bytes(ctx.fields, live, slots),
        ctx.peaks)
    return 100.0 * least / (took_ms / 1e3)
