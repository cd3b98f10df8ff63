"""Where compiled programs are kept between runs — the ONE place this
repo touches JAX's persistent compilation cache.

A 3B-geometry train step compiles for the better part of a minute on a
TPU and every driver compiles it at least twice (the AOT compile behind
``TelemetryRun.attach_step_hlo``, then jit's own), so the cache is on for
accelerator runs.  The directory is part of the cache key, which is why
it is never a temp name:

  * ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads it itself; nothing is
    set in code, so whoever placed the cache from outside owns it.
  * unset — one fixed, git-ignored directory in the checkout
    (:data:`DEFAULT_DIR`), which keeps every compiled program.
  * the platform forced to CPU (``JAX_PLATFORMS=cpu`` or
    :func:`..utils.mesh.use_cpu_devices`) — no cache: CPU runs are the
    test tier, their programs are small, and the checkout the chip tool
    copies must not fill up with them.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compile_cache() -> None:
    """Apply the rule above.  Idempotent, and touches no backend: the
    package calls it at import, and ``use_cpu_devices`` again once it has
    forced the platform."""
    if os.environ.get(CACHE_ENV):
        return
    forced_cpu = (jax.config.jax_platforms or "").split(",")[0] == "cpu"
    path = None if forced_cpu else str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    if path:
        # keep every program, not only those past JAX's one-second write
        # threshold: a run's 150-odd small programs add up to half a
        # minute on a v5e, and ones that straddle the threshold would make
        # a warm run add entries
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
