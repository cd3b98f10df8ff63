"""Median, over the completed requests due inside the window, of the mean
gap between a request's output tokens: (t_done - t_first)/(tokens - 1)."""
from benchmarks.harness import percentile

UNIT = "ms"
RUNNERS = ("serve",)


def read(ctx):
    if ctx.counters["backlog"]:
        return None
    gaps = [1e3 * (r["t_done_s"] - r["t_first_s"]) / (r["n_tokens"] - 1)
            for r in ctx.counters["requests"]
            if r["t_done_s"] is not None and r["n_tokens"] > 1]
    return percentile(gaps, 50) if gaps else None
