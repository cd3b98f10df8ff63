"""Memory accounting: twin of reference ``training_utils/memory.py`` (component
sizes in MB by tensor-walking) plus device-allocator stats from the XLA client
(what ``torch.cuda.memory_allocated / max_memory_allocated`` is to the
reference, ``device.memory_stats()`` is here — reference
``DDP/training_utils/memory.py:8-50``, ``fsdp/utils.py:204-219``).

CPU-simulated devices expose no allocator stats (``memory_stats()`` is None);
every accessor degrades to zeros there so the same scripts run on the CI
mesh.  A TPU v5e reports them: ``bytes_limit`` 15.75 GiB, and
``peak_bytes_in_use`` for live buffers only — a program's temporaries are
counted under ``peak_bytes_reserved``, which these accessors do not read.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

MB = 1024**2
GB = 1024**3


def classify_failure(e: Exception) -> tuple[str, str]:
    """(kind, message) for a benchmark-sweep failure: kind is ``"oom"``
    only when XLA's own verdict says so — anything else is a real error
    and must not be published as the memory edge (a transient compile
    bug would otherwise masquerade as the OOM wall).  The ONE place the
    OOM pattern lives, shared by every sweep script."""
    import re
    msg = str(e)
    m = re.search(r"(Ran out of memory|RESOURCE_EXHAUSTED)[^\n]*", msg)
    if m:
        return "oom", m.group(0)[:200]
    return "error", f"{type(e).__name__}: {msg[:200]}"


def parse_hbm_oom(msg: str) -> tuple[float, float] | None:
    """``(needed_gb, capacity_gb)`` from XLA's HBM verdict — the
    ``Used X.XXG of Y.YYG hbm`` clause its compile- and runtime-OOM
    messages both carry — or None when the text carries no such verdict.
    The ONE place this regex lives: ``scripts/memory_waterline.py``
    and the memory planner's compiler-OOM fallback both parse through
    here."""
    import re
    m = re.search(r"Used ([\d.]+)G(?:iB)? of ([\d.]+)G(?:iB)? hbm", msg)
    if m:
        return float(m.group(1)), float(m.group(2))
    return None


def hbm_capacity_gb(device: jax.Device | None = None) -> float | None:
    """Per-device accelerator memory capacity in GB from the allocator's
    ``bytes_limit``, or None where the backend exposes none (CPU sim) —
    the planner's default ``--hbm-budget-gb`` when the user names no
    budget."""
    limit = device_memory_stats(device)["bytes_limit"]
    return limit / GB if limit else None


def tree_size_bytes(tree: Any) -> int:
    """Total bytes of all array leaves (tensor-walk twin of
    ``memory.py:8-34``)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        if hasattr(leaf, "nbytes"):
            total += leaf.nbytes
        elif hasattr(leaf, "size") and hasattr(leaf, "dtype"):
            total += leaf.size * jnp.dtype(leaf.dtype).itemsize
    return total


def tree_size_mb(tree: Any) -> float:
    """`tree_size_bytes` in MB."""
    return tree_size_bytes(tree) / MB


def tree_local_size_mb(tree: Any) -> float:
    """Size of the *locally addressable* shards of all leaves, in MB — what
    one device actually holds.  For a ZeRO-sharded optimizer state this is
    ~1/ws of ``tree_size_mb``; that delta is the reference's A/B "pass
    signal" (``zero/zero1.py:316-324``)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        shards = getattr(leaf, "addressable_shards", None)
        if shards:
            local_dev_ids = {s.device.id for s in shards}
            # per-device bytes: one device's worth of addressable data
            per_dev = sum(s.data.nbytes for s in shards) / max(len(local_dev_ids), 1)
            total += per_dev
        elif hasattr(leaf, "nbytes"):
            total += leaf.nbytes
    return total / MB


def device_memory_stats(device: jax.Device | None = None) -> dict[str, int]:
    """Allocator stats for one device: ``bytes_in_use`` / ``peak_bytes_in_use``
    / ``bytes_limit`` (zeros when the backend exposes none, e.g. CPU sim)."""
    device = device or jax.local_devices()[0]
    stats = device.memory_stats() or {}
    return {
        "bytes_in_use": int(stats.get("bytes_in_use", 0)),
        "peak_bytes_in_use": int(stats.get("peak_bytes_in_use", 0)),
        "bytes_limit": int(stats.get("bytes_limit", 0)),
    }


def peak_memory_gb(device: jax.Device | None = None) -> float:
    return device_memory_stats(device)["peak_bytes_in_use"] / GB


def all_devices_memory_gb() -> dict[str, dict[str, float]]:
    """Per-device current/peak GB, twin of ``gpu_memory_usage_all``
    (``fsdp/utils.py:204-219``).  Delegates to the memory ledger's one
    shared sampler (``telemetry.memledger.get_sampler``) so every
    consumer polls the allocator through the same site.  Lazy import:
    memledger imports this module."""
    from ..telemetry.memledger import get_sampler
    return get_sampler().all_devices_gb()


def print_memory_stats(
    tag: str,
    params: Any = None,
    grads: Any = None,
    opt_state: Any = None,
    *,
    printer=print,
) -> dict[str, float]:
    """Component-wise MB + allocator totals, twin of ``print_memory_stats``
    (``DDP/training_utils/memory.py:37-50``).  Returns the dict it prints so
    tests/A-B comparisons can assert on it."""
    stats = {}
    if params is not None:
        stats["model_mb"] = tree_size_mb(params)
    if grads is not None:
        stats["grads_mb"] = tree_size_mb(grads)
    if opt_state is not None:
        stats["optimizer_mb"] = tree_size_mb(opt_state)
    dev = device_memory_stats()
    stats["device_in_use_mb"] = dev["bytes_in_use"] / MB
    stats["device_peak_mb"] = dev["peak_bytes_in_use"] / MB
    parts = " | ".join(f"{k}={v:,.1f}" for k, v in stats.items())
    printer(f"[memory:{tag}] {parts}")
    return stats
