"""Kernels: the share of the window's decode steps whose linear layers'
recurrence was the step kernel, which moves a live state once in and once
out in place and touches no other, ``lin_step_inplace_steps /
decode_steps`` of the engine's own counters.  An engine has one decode
program, so this reads 100 or 0; the engine counts it only where the
block has a linear mixer, so a block without one gives nothing."""
LAYER = "kernels"
UNIT = "%"
MOVES = "serve_tokens_per_s"
RUNNERS = ("serve",)


def read(ctx):
    s = ctx.counters["stats"]
    if s.get("lin_step_inplace_steps") is None or not s.get("decode_steps"):
        return None
    return 100.0 * s["lin_step_inplace_steps"] / s["decode_steps"]
