"""Scheduler: the share of its context a window layer still reads, over the
window's decode steps: the engine's ``window_rows_read / full_rows_read``,
both counted once a step and live slot (``min(len, sliding_window)`` against
``len``).  100 when no request has passed the window, so how hard the
traffic works the window layers' page class.  A DESCRIPTOR OF THE CELL'S
TRAFFIC, not a lever of the layer: the mix's context lengths and the
configuration's ``sliding_window`` set it, no change to the program should
move it, and what the window kernels and the ring gain over a full layer
scales with 100 minus it.  ``better`` must name a direction: lower, as the
window layers then read less of their context.  A program without the
counters gives nothing."""
LAYER = "scheduler"
UNIT = "%"
MOVES = "serve_tokens_per_s"
RUNNERS = ("serve",)


def read(ctx):
    s = ctx.counters["stats"]
    if not s.get("full_rows_read"):
        return None
    return 100.0 * s["window_rows_read"] / s["full_rows_read"]
