"""Kernels: bytes a decode step must read (every weight once plus the KV
the batch's live requests actually hold: the architecture's counts) at the
HBM peak, over the traced device time of the decode program per launch.
Memory bound."""
from benchmarks.layer_metrics import _programs

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_tpot_p50_ms"
RUNNERS = ("serve",)
COUNTS = ("decode_step_bytes",)


def read(ctx):
    c = ctx.counters
    took = _programs.device_seconds_per_launch(ctx, "decode")
    if took is None or not c["kv_samples"]:
        return None
    valid_kv = c["kv_valid_sum"] / c["kv_samples"]
    least = ctx.counts.decode_step_bytes(ctx.fields, valid_kv) \
        / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / took
