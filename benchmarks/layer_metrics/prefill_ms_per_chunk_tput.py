"""``prefill_ms_per_chunk``, in a serving cell that is judged on
tokens per second."""
from benchmarks.layer_metrics.prefill_ms_per_chunk import (  # noqa: F401
    LAYER, RUNNERS, UNIT, read)

MOVES = "serve_tokens_per_s"
