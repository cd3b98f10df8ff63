"""``engine_batch_occupancy``, in a serving cell that is judged on
tokens per second."""
from benchmarks.layer_metrics.engine_batch_occupancy import (  # noqa: F401
    LAYER, RUNNERS, UNIT, read)

MOVES = "serve_tokens_per_s"
