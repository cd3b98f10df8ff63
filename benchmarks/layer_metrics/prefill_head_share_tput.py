"""Model step: the share of the window's prefill chunks that ended a
prompt and so ran the final norm, the head and the sampler; the rest skip
them on the device.  ``prefill_head_chunks / prefill_chunks`` of the
engine's own counters: how far a cell's traffic engages that branch
(one chunk in a long prompt's many, or nearly every chunk of short
ones).  A DESCRIPTOR OF THE CELL'S TRAFFIC, not a lever of the layer: the
mix's prompt lengths and the engine's chunk size set it, no change to the
program should move it (one that does has changed what the window holds),
and what the head's skip gains in ``prefill_ms_per_chunk_tput`` scales
with 100 minus it.  ``better`` must name a direction: lower, as more
chunks then skip the head.  A program without the counter, or a window
without a chunk, gives nothing."""
LAYER = "model step"
UNIT = "%"
MOVES = "serve_tokens_per_s"
RUNNERS = ("serve",)


def read(ctx):
    s = ctx.counters["stats"]
    if s.get("prefill_head_chunks") is None or not s.get("prefill_chunks"):
        return None
    return 100.0 * s["prefill_head_chunks"] / s["prefill_chunks"]
