"""Scheduler: the share of the window's (row, chosen expert) pairs whose
expert this program holds, ``moe_assignments_held / moe_assignments`` of
the engine's own counters over the decode steps: 100 where every expert of
the router is held (a model served whole on one chip), a few percent where
the chip holds one rank's share of an expert-parallel layer.  A DESCRIPTOR
OF THE CONFIGURATION, not a lever of the layer: the held experts and the
router's width set it and no change to the program should move it; it says
which regime the grouped product runs in (every choice a visit, or most
choices absent).  ``better`` must name a direction: higher, as more of the
routing's work is then done here.  A program without the counters gives
nothing."""
LAYER = "scheduler"
UNIT = "%"
MOVES = "serve_tokens_per_s"
RUNNERS = ("serve",)


def read(ctx):
    s = ctx.counters["stats"]
    if not s.get("moe_assignments"):
        return None
    return 100.0 * s["moe_assignments_held"] / s["moe_assignments"]
