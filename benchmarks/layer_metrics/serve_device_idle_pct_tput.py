"""``serve_device_idle_pct``, in a serving cell that is judged on
tokens per second."""
from benchmarks.layer_metrics.serve_device_idle_pct import (  # noqa: F401
    LAYER, RUNNERS, UNIT, read)

MOVES = "serve_tokens_per_s"
