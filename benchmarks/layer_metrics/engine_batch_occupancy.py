"""Scheduler: mean share of the decode batch's slots that hold a live
request at the end of a round, ``occupancy_sum / rounds / max_batch``."""
LAYER = "scheduler"
UNIT = "%"
MOVES = "serve_tpot_p50_ms"
RUNNERS = ("serve",)


def read(ctx):
    s = ctx.counters["stats"]
    if not s["rounds"]:
        return None
    return 100.0 * s["occupancy_sum"] / s["rounds"] \
        / ctx.counters["engine"]["max_batch"]
