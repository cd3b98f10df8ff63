"""``decode_ms_per_step``, in a serving cell that is judged on
tokens per second."""
from benchmarks.layer_metrics.decode_ms_per_step import (  # noqa: F401
    LAYER, RUNNERS, UNIT, read)

MOVES = "serve_tokens_per_s"
