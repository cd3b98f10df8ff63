"""``d2h_reads_per_round``, in a serving cell that is judged on tokens per
second."""
from benchmarks.layer_metrics.d2h_reads_per_round import (  # noqa: F401
    LAYER, RUNNERS, UNIT, read)

MOVES = "serve_tokens_per_s"
