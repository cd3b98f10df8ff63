"""Scheduler: the share of its context a window layer still reads, over the
window's decode steps: the engine's ``window_rows_read / full_rows_read``,
both counted once a step and live slot (``min(len, sliding_window)`` against
``len``).  100 when no request has passed the window, so how hard the
traffic works the window layers' page class.  A program without the counters
gives nothing."""
LAYER = "scheduler"
UNIT = "%"
MOVES = "serve_tokens_per_s"
RUNNERS = ("serve",)


def read(ctx):
    s = ctx.counters["stats"]
    if not s.get("full_rows_read"):
        return None
    return 100.0 * s["window_rows_read"] / s["full_rows_read"]
