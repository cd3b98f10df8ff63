"""Memory planner: pre-flight HBM waterline prediction, auto-fit search
over remat × accumulation × quantization × offload, and contracted host
offload of optimizer state / remat activations.

Three layers (the OOM wall a 16 GB chip puts across the knob space):

  * ``predictor`` — per-config waterline without running a step:
    compile-based (``memory_analysis()`` / the compiler's own
    used-vs-capacity OOM verdict) with an analytic tensor-walk fallback;
  * ``planner`` — reject predicted-over-budget configs *pre-compile* and
    rank the survivors by modeled throughput (bench-JSON priors when
    measured rows exist);
  * ``offload`` — host memory-kind placements for optimizer state and
    named remat activations, with an :class:`OffloadPlan` declaring the
    per-step transfer counts so ``analysis/hlo_lint`` can expect them
    instead of flagging them.
"""

from .offload import (  # noqa: F401
    OFFLOAD_MODES,
    OffloadPlan,
    offload_tree,
    plan_offload,
    stream_tree,
    supports_host_offload,
)
from .planner import (  # noqa: F401
    Candidate,
    NoFittingConfig,
    Plan,
    PlannedCandidate,
    enumerate_candidates,
    load_bench_priors,
    parse_bench_config_name,
    plan,
)
from .predictor import (  # noqa: F401
    MEMORY_PRIORS_SCHEMA_VERSION,
    WaterlinePrediction,
    analytic_waterline,
    load_memory_priors,
    predict,
    predict_from_step,
)
