"""Kernels: the least time one chip could take for the decode attention the
WINDOW layers require in a step (the architecture's counts at the engine's
``window_rows_read / decode_steps``, the cached rows the live slots' windows
hold, ``min(len, sliding_window)`` each: per such layer and row its K and V
bytes once and 4 x head_dim FLOPs a query head, plus the slots' queries in
and outputs out) over ``swa_decode_attn_ms_tput``.  Memory bound: 6 query
heads share a KV head.  The kernel copies whole blocks of 256 positions and
the counts hold the visible rows alone, so the share cannot pass 100."""
from benchmarks import harness
from benchmarks.layer_metrics import swa_decode_attn_ms_tput

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_tokens_per_s"
RUNNERS = ("serve",)
COUNTS = ("window_decode_attention_flops", "window_decode_attention_bytes")


def read(ctx):
    s = ctx.counters["stats"]
    took_ms = swa_decode_attn_ms_tput.read(ctx)
    if took_ms is None or not s.get("window_rows_read") \
            or not s.get("decode_steps") or not s.get("rounds"):
        return None
    rows = s["window_rows_read"] / s["decode_steps"]
    slots = s["occupancy_sum"] / s["rounds"]
    least, _ = harness.roofline_seconds(
        ctx.counts.window_decode_attention_flops(ctx.fields, rows),
        ctx.counts.window_decode_attention_bytes(ctx.fields, rows, slots),
        ctx.peaks)
    return 100.0 * least / (took_ms / 1e3)
