"""Kernels: the least time one chip could take for the chunked scan of a
prefill chunk (the architecture's counts at the scan's stated sub-chunk,
for the VALID rows a chunk held: the engine's ``lin_scan_rows /
prefill_chunks``) over ``lin_scan_ms_tput``.  Either peak may bound it:
the counts decide."""
from benchmarks import harness
from benchmarks.layer_metrics import lin_scan_ms_tput

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_tokens_per_s"
RUNNERS = ("serve",)
COUNTS = ("chunk_scan_flops", "chunk_scan_bytes")


def read(ctx):
    s = ctx.counters["stats"]
    took_ms = lin_scan_ms_tput.read(ctx)
    if took_ms is None or not s.get("lin_scan_rows") \
            or not s.get("prefill_chunks"):
        return None
    rows = s["lin_scan_rows"] / s["prefill_chunks"]
    least, _ = harness.roofline_seconds(
        ctx.counts.chunk_scan_flops(ctx.fields, rows),
        ctx.counts.chunk_scan_bytes(ctx.fields, rows), ctx.peaks)
    return 100.0 * least / (took_ms / 1e3)
