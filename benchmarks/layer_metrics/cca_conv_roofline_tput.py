"""Kernels: the least time one chip could take for what a decode step's
convolutions, q-k mean, value shift and tail update must move (the
architecture's counts at the engine's live slots: both stages' weights
once a layer, every live slot's tail read once and written once, the
latents in and q', k', v out) at the HBM peak, over ``cca_conv_ms_tput``.
Memory side only: the grouped stage's products are a few MFLOP a step.  It
says how launch-bound the chain of small ops is, and is the yardstick a
kernel for it would be held to."""
from benchmarks.layer_metrics import cca_conv_ms_tput

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_tokens_per_s"
RUNNERS = ("serve",)
COUNTS = ("cca_conv_bytes",)


def read(ctx):
    s = ctx.counters["stats"]
    took_ms = cca_conv_ms_tput.read(ctx)
    if took_ms is None or not s.get("conv_tail_slot_steps") \
            or not s.get("decode_steps"):
        return None
    live = s["conv_tail_slot_steps"] / s["decode_steps"]
    least = ctx.counts.cca_conv_bytes(ctx.fields, live, live) \
        / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (took_ms / 1e3)
