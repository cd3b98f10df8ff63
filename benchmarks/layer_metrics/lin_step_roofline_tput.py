"""Kernels: the bytes a decode step must move for the recurrent state (the
architecture's counts: every LIVE slot's state and conv tail read once and
written once in every linear layer; live slots from the engine's
``state_slot_steps / decode_steps``) at the HBM peak, over
``lin_step_ms_tput``.  Memory bound: a step does about three operations a
state element it moves."""
from benchmarks.layer_metrics import lin_step_ms_tput

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_tokens_per_s"
RUNNERS = ("serve",)
COUNTS = ("state_step_bytes",)


def read(ctx):
    s = ctx.counters["stats"]
    took_ms = lin_step_ms_tput.read(ctx)
    if took_ms is None or not s.get("state_slot_steps") \
            or not s.get("decode_steps"):
        return None
    live = s["state_slot_steps"] / s["decode_steps"]
    least = ctx.counts.state_step_bytes(ctx.fields, live) \
        / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (took_ms / 1e3)
