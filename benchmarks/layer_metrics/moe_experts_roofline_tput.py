"""Kernels: the bytes of the held experts that got a token (the engine's
``moe_experts_touched`` per decode step x one expert's three matrices, the
architecture's counts) at the HBM peak, over ``moe_experts_ms_tput``.
Memory bound: at about 2 tokens an expert the product reads far more than
it multiplies."""
from benchmarks.layer_metrics import moe_experts_ms_tput

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_tokens_per_s"
RUNNERS = ("serve",)
COUNTS = ("expert_step_bytes",)


def read(ctx):
    s = ctx.counters["stats"]
    took_ms = moe_experts_ms_tput.read(ctx)
    if took_ms is None or not s.get("moe_experts_touched") \
            or not s.get("decode_steps"):
        return None
    touched = s["moe_experts_touched"] / s["decode_steps"]
    least = ctx.counts.expert_step_bytes(ctx.fields, touched) \
        / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (took_ms / 1e3)
