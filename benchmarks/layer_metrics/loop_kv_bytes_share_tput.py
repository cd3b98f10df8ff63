"""Model step: which of the two a change to a looped block's decode step
must attack: the bytes of the cached K/V rows the live requests hold (one
row a (pass, layer) cache a token, at the window's mean valid K/V, the
runner's ``kv_valid_sum / kv_samples``) as a share of ALL the bytes the
step requires (those rows and every layer's weights once a pass, the head
and the embedding's rows: the architecture's counts).  From counters alone:
no trace is read.  A DESCRIPTOR of the cell's sizes more than a lever: the
batch, the contexts and the passes set it; a change that moves it has
changed what a step must read.  ``better`` must name a direction: lower, as
more of a step is then weights, which a larger batch amortises.  A program
without the looped block's counters gives nothing."""
LAYER = "model step"
UNIT = "%"
MOVES = "serve_tokens_per_s"
RUNNERS = ("serve",)
COUNTS = ("decode_step_bytes", "kv_bytes_per_token")


def read(ctx):
    c, s = ctx.counters, ctx.counters["stats"]
    if not c.get("kv_samples") or not s.get("ut_passes"):
        return None
    valid = c["kv_valid_sum"] / c["kv_samples"]
    rows = s["occupancy_sum"] / s["rounds"] if s.get("rounds") else 0.0
    return 100.0 * valid * ctx.counts.kv_bytes_per_token(ctx.fields) \
        / ctx.counts.decode_step_bytes(ctx.fields, valid, rows=rows)
