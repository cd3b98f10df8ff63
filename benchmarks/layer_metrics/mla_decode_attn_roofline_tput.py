"""Kernels: the least time one chip could take for the absorbed decode
attention a step requires (the architecture's counts: per layer, head and
live cached position 2 x (rank + rope) + 2 x rank FLOPs and one latent
row's bytes, plus the slots' absorbed queries in and ``o~`` out; live
positions from the runner's ``kv_valid_sum / kv_samples``, slots from the
engine's occupancy) over the decode program's ``attn_core`` self time per
launch: the latent paged kernel.  On the v5e's ridge, so either peak may
bound it."""
from benchmarks import harness
from benchmarks.layer_metrics import _scopes

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_tokens_per_s"
RUNNERS = ("serve",)
COUNTS = ("latent_decode_attention_flops", "latent_decode_attention_bytes")


def read(ctx):
    c = ctx.counters
    took_ms = _scopes.scope_ms_per_launch(ctx, ("attn_core",), "decode")
    s = c["stats"]
    if took_ms is None or not c["kv_samples"] or not s["rounds"]:
        return None
    live = c["kv_valid_sum"] / c["kv_samples"]
    slots = s["occupancy_sum"] / s["rounds"]
    least, _ = harness.roofline_seconds(
        ctx.counts.latent_decode_attention_flops(ctx.fields, live),
        ctx.counts.latent_decode_attention_bytes(ctx.fields, live, slots),
        ctx.peaks)
    return 100.0 * least / (took_ms / 1e3)
