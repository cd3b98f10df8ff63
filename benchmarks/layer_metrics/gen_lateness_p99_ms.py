"""Load generator: 99th percentile of how late a request was handed to the
engine after it fell due.  The generator hands requests over between
rounds, so this is about one round; a time to first token counts from the
due time and includes it."""
from benchmarks.harness import percentile

LAYER = "load generator"
UNIT = "ms"
MOVES = "serve_tpot_p50_ms"
RUNNERS = ("serve",)


def read(ctx):
    late = ctx.counters["lateness_s"]
    if ctx.counters["backlog"] or not late:
        return None
    return 1e3 * percentile(late, 99)
