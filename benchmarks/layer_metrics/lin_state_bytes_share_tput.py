"""Model step: how much of a decode step is the mechanism: the bytes the
live slots' recurrent state requires (the architecture's counts at the
engine's ``state_slot_steps / decode_steps`` live slots: read once and
written once in every linear layer) as a share of ALL the bytes the step
requires (weights of what was touched, ``moe_experts_touched /
decode_steps`` held experts among them, the live K/V rows the runner
sampled, and that state).  From counters alone: no trace is read.  A
program without the counters gives nothing."""
LAYER = "model step"
UNIT = "%"
MOVES = "serve_tokens_per_s"
RUNNERS = ("serve",)
COUNTS = ("state_step_bytes", "decode_step_bytes")


def read(ctx):
    c, s = ctx.counters, ctx.counters["stats"]
    if not s.get("state_slot_steps") or not s.get("decode_steps") \
            or not c.get("kv_samples"):
        return None
    steps = s["decode_steps"]
    live = s["state_slot_steps"] / steps
    touched = {} if s.get("moe_experts_touched") is None else {
        "experts_touched": s["moe_experts_touched"] / steps}
    whole = ctx.counts.decode_step_bytes(
        ctx.fields, c["kv_valid_sum"] / c["kv_samples"], live_slots=live,
        **touched)
    return 100.0 * ctx.counts.state_step_bytes(ctx.fields, live) / whole
