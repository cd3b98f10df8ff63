"""Kernels: the least time one chip could take for the decode attention the
FULL layers require in a step (the architecture's counts at the engine's
``full_rows_read / decode_steps``, every cached row of the live slots) over
the decode program's ``attn_paged`` self time per launch, which this block
opens round its full layers as the hybrid with expert layers does
(``_attnscopes``).  Memory bound; whole blocks are copied and the visible
rows counted, so the share cannot pass 100."""
from benchmarks import harness
from benchmarks.layer_metrics import _attnscopes

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_tokens_per_s"
RUNNERS = ("serve",)
COUNTS = ("full_decode_attention_flops", "full_decode_attention_bytes")


def read(ctx):
    s = ctx.counters["stats"]
    took_ms = _attnscopes.subscope_ms_per_launch(ctx, ("attn_paged",),
                                                 "decode")
    if took_ms is None or not s.get("full_rows_read") \
            or not s.get("decode_steps") or not s.get("rounds"):
        return None
    rows = s["full_rows_read"] / s["decode_steps"]
    slots = s["occupancy_sum"] / s["rounds"]
    least, _ = harness.roofline_seconds(
        ctx.counts.full_decode_attention_flops(ctx.fields, rows),
        ctx.counts.full_decode_attention_bytes(ctx.fields, rows, slots),
        ctx.peaks)
    return 100.0 * least / (took_ms / 1e3)
