"""Scheduler: host time a round spends admitting and book-keeping,
``(admit_s + bookkeep_s) / rounds`` of the engine's own counters."""
LAYER = "scheduler"
UNIT = "ms"
MOVES = "serve_tpot_p50_ms"
RUNNERS = ("serve",)


def read(ctx):
    s = ctx.counters["stats"]
    if not s["rounds"]:
        return None
    return 1e3 * (s["admit_s"] + s["bookkeep_s"]) / s["rounds"]
