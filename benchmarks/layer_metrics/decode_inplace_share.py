"""Kernels: the share of the window's decode steps whose attention read
the live KV pages in place (the paged decode kernel) and built no gather
view, ``decode_inplace_steps / decode_steps`` of the engine's own
counters.  An engine has one decode program, so this reads 100 or 0; a
program without the counter gives nothing."""
LAYER = "kernels"
UNIT = "%"
MOVES = "serve_tpot_p50_ms"
RUNNERS = ("serve",)


def read(ctx):
    s = ctx.counters["stats"]
    if s.get("decode_inplace_steps") is None or not s.get("decode_steps"):
        return None
    return 100.0 * s["decode_inplace_steps"] / s["decode_steps"]
