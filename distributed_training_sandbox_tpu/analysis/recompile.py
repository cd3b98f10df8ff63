"""Retrace/recompile detection for step functions.

The classic silent perf killer: a step function that retraces every call
(weak-typed scalars changing dtype, Python-varying shapes, a config
object failing ``__hash__`` stability) turns a 10 ms step into a
multi-second compile, and nothing *fails* — throughput just dies.  The
reference course never guards this; here it is a checkable property:
run a few steps and assert the jit cache stopped growing after the
first executed call.

Uses the jitted callable's ``_cache_size()``; a handle without one (not
a jit wrapper) reports ``supported=False`` rather than failing the caller.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable


def jit_cache_size(fn) -> int | None:
    """Current compilation-cache entry count of a jitted callable, or
    None when the handle doesn't expose one (not a jit wrapper)."""
    probe = getattr(fn, "_cache_size", None)
    if callable(probe):
        try:
            return int(probe())
        except Exception:
            return None
    return None


@dataclass
class RecompileReport:
    steps: int
    cache_sizes: list = field(default_factory=list)  # after each call
    supported: bool = True

    @property
    def retraces_after_settle(self) -> int:
        """New traces after step 1.  Step 0 is the expected compile;
        step 1 may legitimately retrace once when the step's outputs
        (committed, sharded) replace the host-built inputs — exactly
        what every train loop does on its first iteration.  Growth from
        step 1 onward is a real per-step recompile."""
        if len(self.cache_sizes) < 2:
            return 0
        return self.cache_sizes[-1] - self.cache_sizes[1]

    @property
    def ok(self) -> bool:
        return (not self.supported) or self.retraces_after_settle == 0

    def summary(self) -> str:
        if not self.supported:
            return "SKIPPED (no _cache_size on this callable)"
        if self.ok:
            return (f"OK (cache settled at {self.cache_sizes[-1]} "
                    f"over {self.steps} steps)"
                    if self.cache_sizes else f"OK ({self.steps} steps)")
        return (f"RECOMPILED {self.retraces_after_settle}x after step 1 "
                f"(cache sizes per step: {self.cache_sizes})")

    def to_dict(self) -> dict:
        return {"steps": self.steps, "cache_sizes": self.cache_sizes,
                "supported": self.supported, "ok": self.ok,
                "retraces_after_settle": self.retraces_after_settle}


def watch_recompiles(step_fn: Callable, args: tuple, *, n_steps: int = 4,
                     advance: Callable | None = None) -> RecompileReport:
    """Run ``step_fn(*args)`` for ``n_steps`` and report cache growth.

    ``advance(args, outputs) -> next_args`` feeds the step's outputs back
    into its inputs (required when the step donates its state buffers —
    re-calling with consumed arrays is an error).  Default: same args
    every step (safe only without donation)."""
    sizes = []
    for _ in range(max(n_steps, 2)):
        out = step_fn(*args)
        size = jit_cache_size(step_fn)
        if size is None:
            return RecompileReport(steps=len(sizes) + 1, cache_sizes=sizes,
                                   supported=False)
        sizes.append(size)
        if advance is not None:
            args = advance(args, out)
    return RecompileReport(steps=len(sizes), cache_sizes=sizes)
