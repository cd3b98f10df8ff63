"""Scheduler: mean time from a request falling DUE to its admission into a
slot, ``queue_wait_s / admitted`` of the engine's own counters.  It bears
on the first-token gate; it is filed under the cell's judged metric because
an entry has one ``moves``."""
LAYER = "scheduler"
UNIT = "ms"
MOVES = "serve_tpot_p50_ms"
RUNNERS = ("serve",)


def read(ctx):
    s = ctx.counters["stats"]
    if not s.get("admitted"):
        return None
    return 1e3 * s["queue_wait_s"] / s["admitted"]
