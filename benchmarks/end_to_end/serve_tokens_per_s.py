"""Tokens the engine PROCESSED inside the window, over the window as it was
(it closes on a round boundary, up to one round after ``--seconds``): prompt
tokens prefilled plus tokens generated, of the requests that completed in
the window and of those still resident at its end (the engine is empty when
the window opens).  What an offline job pays for.  Counting completed
requests only would quantise the metric: one document request is 5% of a
window's tokens."""
UNIT = "tokens/s"
RUNNERS = ("serve",)


def read(ctx):
    c = ctx.counters
    if not c["backlog"]:
        return None
    return c["tokens_processed_in_window"] / c["window_actual_s"]
