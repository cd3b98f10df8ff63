from .mesh import (  # noqa: F401
    get,
    make_mesh,
    get_mesh,
    register_mesh,
    setup_distributed,
    shutdown_distributed,
    auto_initialize_from_env,
    bringup_barrier,
    BringupTimeout,
    host_to_global,
    process_local_put,
    local_scalar,
    use_cpu_devices,
)
from .compile_cache import configure_compile_cache  # noqa: F401
from .prng import set_seed, key_for_axis  # noqa: F401
from .memory import (  # noqa: F401
    tree_size_mb,
    tree_local_size_mb,
    device_memory_stats,
    print_memory_stats,
    peak_memory_gb,
    classify_failure,
)
from .tracker import PerformanceTracker  # noqa: F401
from .flops import get_model_flops_per_token  # noqa: F401
from .profiling import SCOPES, ProfileSchedule, Profiler, scope  # noqa: F401
from .config import TrainConfig, build_argparser, build_run_id  # noqa: F401
from . import checkpoint  # noqa: F401  (orbax imported lazily inside)
