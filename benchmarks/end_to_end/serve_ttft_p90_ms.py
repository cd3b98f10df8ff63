"""90th percentile, over the requests due inside the window, of the time
from when a request was DUE to its first token.  A request that never got
one counts for as long as the run waited for it (window plus drain), which
no real first token reaches.

Not a bounded metric of any cell today: at the hundred requests a window
holds below the knee, this percentile spreads by 7-10% from run to run on its
sampling error alone (my chip runs, PR 22), more than a bound may carry.
The cell holds the time to first token as a gate instead (``slo`` in the
traffic file, ``runners/serve.py`` ``first_token_gate``), and
``benchmarks/sweep.py`` reads this to find a knee."""
from benchmarks.harness import percentile

UNIT = "ms"
RUNNERS = ("serve",)


def ttfts_ms(counters) -> list[float]:
    return [1e3 * ((r["t_first_s"] if r["t_first_s"] is not None
                    else counters["end_s"]) - r["due_s"])
            for r in counters["requests"]]


def read(ctx):
    if ctx.counters["backlog"]:
        return None
    return percentile(ttfts_ms(ctx.counters), 90)
