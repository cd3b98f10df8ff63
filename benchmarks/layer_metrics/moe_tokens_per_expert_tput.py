"""Scheduler: tokens a held expert that got any token got, over the
window's decode steps: the engine's ``moe_assignments_held /
moe_experts_touched``.  How near the batch is to the deployment's expert
load (rows a step x experts a token / experts of the layer, a rank: 64
there at 64 rows a rank, 2 here).  A program without the counters gives
nothing."""
LAYER = "scheduler"
UNIT = "tokens"
MOVES = "serve_tokens_per_s"
RUNNERS = ("serve",)


def read(ctx):
    s = ctx.counters["stats"]
    if not s.get("moe_experts_touched"):
        return None
    return s["moe_assignments_held"] / s["moe_experts_touched"]
