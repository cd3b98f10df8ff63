"""Kernels: the whole decode program's share of its roofline in a looped
block: the bytes a decode step must read (the architecture's counts: every
layer's weights once a PASS, the head once, the embedding's rows, and the
K/V rows the live requests hold in every (pass, layer) cache, at the
window's mean valid K/V, the runner's ``kv_valid_sum / kv_samples``) at the
HBM peak, over the traced device time of the decode program per launch.
Memory bound: at ``max_batch`` rows a weight is read for a handful of
multiply-adds.  A block whose counts have no pass (no
``decode_step_weight_bytes``) is not this reader's."""
from benchmarks.layer_metrics import _programs

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_tokens_per_s"
RUNNERS = ("serve",)
COUNTS = ("decode_step_bytes", "decode_step_weight_bytes")


def read(ctx):
    c, s = ctx.counters, ctx.counters["stats"]
    took = _programs.device_seconds_per_launch(ctx, "decode")
    if took is None or not c.get("kv_samples") or not s.get("ut_passes"):
        return None
    rows = s["occupancy_sum"] / s["rounds"] if s.get("rounds") else 0.0
    least = ctx.counts.decode_step_bytes(
        ctx.fields, c["kv_valid_sum"] / c["kv_samples"], rows=rows) \
        / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / took
