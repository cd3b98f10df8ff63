"""``prefill_inplace_share``, in a serving cell that is judged on tokens
per second."""
from benchmarks.layer_metrics.prefill_inplace_share import (  # noqa: F401
    LAYER, RUNNERS, UNIT, read)

MOVES = "serve_tokens_per_s"
