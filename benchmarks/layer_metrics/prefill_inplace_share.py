"""Kernels: the share of the window's prefill chunks whose attention
read the slot's live KV pages in place (the flash prefill kernel) and
built neither a gather view nor a whole-view score tensor,
``prefill_inplace_chunks / prefill_chunks`` of the engine's own
counters.  An engine has one prefill program, so this reads 100 or 0; a
program without the counter gives nothing."""
LAYER = "kernels"
UNIT = "%"
MOVES = "serve_tpot_p50_ms"
RUNNERS = ("serve",)


def read(ctx):
    s = ctx.counters["stats"]
    if s.get("prefill_inplace_chunks") is None or not s.get("prefill_chunks"):
        return None
    return 100.0 * s["prefill_inplace_chunks"] / s["prefill_chunks"]
