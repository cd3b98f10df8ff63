"""``round_idle_read_ms``, in a serving cell that is judged on tokens per
second."""
from benchmarks.layer_metrics.round_idle_read_ms import (  # noqa: F401
    LAYER, RUNNERS, UNIT, read)

MOVES = "serve_tokens_per_s"
