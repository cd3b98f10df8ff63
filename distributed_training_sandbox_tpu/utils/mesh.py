"""Named-mesh runtime: the TPU twin of the reference's process-group layer.

The reference keeps a string-keyed accessor over torch.distributed state —
``get("ws"|"rank"|"lrank"|"pg")`` with an optional registered DeviceMesh
(reference ``DDP/training_utils/utils.py:49-87``).  Here the process group *is*
a ``jax.sharding.Mesh``: construction happens once, meshes are registered by
name, and ``get()`` answers the same questions (world size, process rank,
local device count, the mesh itself, named-axis sizes).

Unlike NCCL there is no per-rank process by default: JAX is SPMD, so
device-level "rank" only exists *inside* ``shard_map`` (``lax.axis_index``,
see ops.collectives.axis_rank).  Host-level rank == ``jax.process_index()``
and is what multi-host (DCN) code keys on.
"""

from __future__ import annotations

import atexit
import math
import os
import re
import socket
import time
from typing import Mapping, Sequence

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .compile_cache import configure_compile_cache

_MESHES: dict[str, Mesh] = {}
DEFAULT_MESH = "default"


class BringupTimeout(RuntimeError):
    """Distributed bring-up did not complete within the budget.

    Raised instead of letting ``jax.distributed.initialize`` hang
    forever when a peer never shows up (crashed before connecting, or
    was never launched) — the coordinator-side twin of a gloo connect
    timeout.  Carries enough context to tell WHICH rendezvous failed."""

    def __init__(self, coordinator: str | None, num_processes: int | None,
                 process_id: int | None, timeout_s: float, cause: str = ""):
        self.coordinator = coordinator
        self.num_processes = num_processes
        self.process_id = process_id
        self.timeout_s = timeout_s
        detail = f": {cause}" if cause else ""
        super().__init__(
            f"distributed bring-up timed out after {timeout_s:.0f}s "
            f"(coordinator={coordinator}, num_processes={num_processes}, "
            f"process_id={process_id}) — a peer is missing or the "
            f"coordinator is unreachable{detail}")


def use_cpu_devices(n: int = 8) -> None:
    """Force this process onto ``n`` simulated CPU devices.

    The CI/test substrate (SURVEY.md §7.1): the twin of the reference running
    gloo on 2 CPU ranks.  Must run before the JAX backend initializes.  When a
    backend is already live this is a no-op if the platform is already cpu.

    If the multi-process launcher's env contract is present
    (``DTS_COORDINATOR``/``DTS_NUM_PROCESSES``/``DTS_PROCESS_ID`` — the
    ``torchrun --nproc_per_node`` twin, set by ``dts-launch run
    --nprocs N``), the process also joins the distributed cluster here,
    so every strategy script's existing ``--cpu-devices`` bootstrap
    becomes multi-process-capable with no per-script changes.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
    if m is None:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()
    elif int(m.group(1)) != n:
        os.environ["XLA_FLAGS"] = flags.replace(
            m.group(0), f"--xla_force_host_platform_device_count={n}")
    jax.config.update("jax_platforms", "cpu")
    configure_compile_cache()
    auto_initialize_from_env()


_DTS_INITIALIZED = False


def auto_initialize_from_env() -> bool:
    """Join the launcher-spawned process group when the ``DTS_*`` env
    contract is set (no-op otherwise; returns whether it initialized).
    Guarded by a module flag, NOT ``jax.process_count()`` — querying the
    backend would initialize it single-process and lock distributed
    bring-up out."""
    global _DTS_INITIALIZED
    coord = os.environ.get("DTS_COORDINATOR")
    nprocs = os.environ.get("DTS_NUM_PROCESSES")
    if not coord or not nprocs or int(nprocs) < 2:
        return False
    if _DTS_INITIALIZED:
        return True
    setup_distributed(coord, num_processes=int(nprocs),
                      process_id=int(os.environ["DTS_PROCESS_ID"]))
    _DTS_INITIALIZED = True
    barrier = os.environ.get("DTS_BRINGUP_TIMEOUT")
    if barrier:
        # --distributed mode: prove every peer actually executes a
        # collective before the driver starts building state.  A peer
        # that connected to the coordinator but wedged before its first
        # psum becomes a StepTimeoutError here — the same exception the
        # elastic supervisor already knows how to restart from.
        bringup_barrier(float(barrier))
    return True


def setup_distributed(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
    *,
    timeout_s: float | None = None,
) -> None:
    """Multi-host (DCN) bring-up: twin of ``dist.init_process_group`` at
    reference ``zero/zero1.py:204``.

    Single-host (the common case here) is a no-op — ICI collectives need no
    process group.  On a multi-host TPU slice JAX auto-detects the topology,
    so all arguments are optional.

    Bring-up is BOUNDED: ``timeout_s`` (default ``DTS_BRINGUP_TIMEOUT``
    or 120s) caps how long ``jax.distributed.initialize`` may wait for
    peers — a missing peer raises :class:`BringupTimeout` instead of
    hanging forever.  A coordinator port still in TIME_WAIT from a
    previous group (EADDRINUSE) is retried in place a few times before
    giving up; rotation to a *fresh* port is the launcher's job (it owns
    port selection).  ``jax.distributed.shutdown`` is registered via
    ``atexit`` so every exit path — clean return, uncaught exception,
    ``sys.exit`` — tears the group down.
    """
    env_procs = os.environ.get("JAX_NUM_PROCESSES")
    if num_processes is None and env_procs is not None:
        num_processes = int(env_procs)
    if num_processes is None or num_processes <= 1:
        return
    if timeout_s is None:
        timeout_s = float(os.environ.get("DTS_BRINGUP_TIMEOUT") or 120.0)
    plats = str(jax.config.jax_platforms
                or os.environ.get("JAX_PLATFORMS", ""))
    if "cpu" in plats:
        # CPU cross-process collectives need an explicit backend;
        # gloo ships with jaxlib (the reference's gloo-on-CPU-ranks
        # mode, modal_utils.py / SURVEY.md §7.1).
        jax.config.update("jax_cpu_collectives_implementation",
                          "gloo")
    if process_id is not None and process_id != 0 and coordinator_address:
        # jaxlib's coordination client converts a RegisterTask deadline
        # into a process-terminating FATAL abort — it never raises into
        # Python.  An unreachable coordinator must therefore be caught
        # BEFORE initialize, with a bounded TCP preflight; once the
        # coordinator accepts, initialize proceeds normally.
        host, _, port = coordinator_address.rpartition(":")
        deadline = time.monotonic() + timeout_s
        while True:
            try:
                socket.create_connection(
                    (host or "127.0.0.1", int(port)),
                    timeout=min(1.0, timeout_s)).close()
                break
            except OSError as e:
                if time.monotonic() >= deadline:
                    raise BringupTimeout(
                        coordinator_address, num_processes, process_id,
                        timeout_s, cause=f"{type(e).__name__}: {e}") from e
                time.sleep(0.2)
    attempts, max_attempts = 0, 3
    while True:
        attempts += 1
        try:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id,
                initialization_timeout=max(1, int(timeout_s)),
            )
            break
        except Exception as e:  # noqa: BLE001 - classified + re-raised
            msg = str(e)
            if ("EADDRINUSE" in msg or "address already in use" in
                    msg.lower()) and attempts < max_attempts:
                # coordinator port lingering in TIME_WAIT from the
                # previous group on the same address — transient
                print(f"[mesh] coordinator port busy "
                      f"({coordinator_address}), retry "
                      f"{attempts}/{max_attempts - 1}")
                time.sleep(0.5 * attempts)
                continue
            if ("DEADLINE_EXCEEDED" in msg or "timed out" in msg.lower()
                    or "timeout" in msg.lower()):
                raise BringupTimeout(coordinator_address, num_processes,
                                     process_id, timeout_s,
                                     cause=msg.splitlines()[0]) from e
            raise
    atexit.register(shutdown_distributed)


def shutdown_distributed() -> None:
    """Idempotent ``jax.distributed.shutdown`` — the teardown half of
    :func:`setup_distributed`, safe to call from a ``finally`` on any
    exit path (and registered via ``atexit`` so interpreter exit covers
    the paths no ``finally`` reaches).  A failed shutdown is reported,
    not raised: teardown must never mask the error that caused it."""
    global _DTS_INITIALIZED
    if not jax.distributed.is_initialized():
        _DTS_INITIALIZED = False
        return
    try:
        jax.distributed.shutdown()
    except Exception as e:  # noqa: BLE001 - teardown must not mask errors
        print(f"[mesh] WARNING: jax.distributed.shutdown failed: "
              f"{type(e).__name__}: {e}")
    _DTS_INITIALIZED = False


def bringup_barrier(timeout_s: float = 120.0) -> None:
    """Cross-process bring-up barrier: one tiny psum over EVERY device,
    run under the elastic :class:`~..resilience.elastic.Watchdog` so a
    peer that wedges after connecting surfaces as the same
    ``StepTimeoutError`` the step-level watchdog raises — one timeout
    machinery for bring-up and steady state.  Verifies the sum, so a
    short-changed mesh (a peer initialized with fewer devices than the
    group believes) is caught here, not ten minutes into training."""
    from ..resilience.elastic import Watchdog

    def _sync() -> float:
        devs = np.asarray(jax.devices())
        mesh = Mesh(devs, ("all",))
        ones = host_to_global(np.ones((devs.size,), np.float32),
                              mesh, PartitionSpec("all"))
        total = jax.jit(lambda x: x.sum(),
                        out_shardings=NamedSharding(mesh, PartitionSpec())
                        )(ones)
        return local_scalar(total)

    wd = Watchdog(timeout_s=timeout_s)
    total = wd.block(_sync, step=-1)
    ndev = len(jax.devices())
    if int(total) != ndev:
        raise RuntimeError(
            f"bring-up barrier mismatch: psum saw {int(total)} devices, "
            f"backend reports {ndev} — mesh does not span the group")


def make_mesh(
    axes: Mapping[str, int] | None = None,
    *,
    devices: Sequence[jax.Device] | None = None,
    name: str = DEFAULT_MESH,
    register: bool = True,
) -> Mesh:
    """Build a named device mesh.  ``axes`` maps axis name -> size; one size
    may be -1 (fills with the remaining devices).  Default: 1-D ``dp`` mesh
    over every device.
    """
    devs = np.asarray(devices if devices is not None else jax.devices())
    if axes is None:
        axes = {"dp": devs.size}
    names = tuple(axes.keys())
    sizes = list(axes.values())
    if sizes.count(-1) > 1:
        raise ValueError("at most one mesh axis may be -1")
    if -1 in sizes:
        known = math.prod(s for s in sizes if s != -1)
        if devs.size % known:
            raise ValueError(f"{devs.size} devices not divisible by {known}")
        sizes[sizes.index(-1)] = devs.size // known
    total = math.prod(sizes)
    if total > devs.size:
        raise ValueError(f"mesh {dict(zip(names, sizes))} needs {total} devices, "
                         f"have {devs.size}")
    mesh = Mesh(devs.flatten()[:total].reshape(sizes), names)
    if register:
        _MESHES[name] = mesh
    return mesh


def register_mesh(name: str, mesh: Mesh) -> Mesh:
    """Twin of the reference's ``cache_mesh`` decorator registry
    (``DDP/training_utils/utils.py:49-60``)."""
    _MESHES[name] = mesh
    return mesh


def get_mesh(name: str = DEFAULT_MESH) -> Mesh:
    if name not in _MESHES:
        if name == DEFAULT_MESH:
            return make_mesh()
        raise KeyError(f"no mesh registered under {name!r}; "
                       f"have {sorted(_MESHES)}")
    return _MESHES[name]


def get(what: str, mesh_name: str = DEFAULT_MESH):
    """String-keyed runtime accessor, twin of reference
    ``DDP/training_utils/utils.py:63-87``.

    Keys:
      "ws"     -> world size: total device count of the mesh
      "rank"   -> host/process rank (``jax.process_index()``)
      "nprocs" -> process count
      "lrank"  -> local device count on this host
      "pg" | "mesh" -> the named ``Mesh`` (the process-group analogue)
      "axis:<name>" -> size of that mesh axis
    """
    if what in ("pg", "mesh"):
        return get_mesh(mesh_name)
    if what == "ws":
        return int(get_mesh(mesh_name).devices.size)
    if what == "rank":
        return jax.process_index()
    if what == "nprocs":
        return jax.process_count()
    if what == "lrank":
        return len(jax.local_devices())
    if what.startswith("axis:"):
        axis = what.split(":", 1)[1]
        return int(get_mesh(mesh_name).shape[axis])
    raise KeyError(f"unknown runtime key {what!r}")


def host_to_global(arr, mesh: Mesh, spec: PartitionSpec) -> jax.Array:
    """A host-identical value (same on every process, e.g. identically
    seeded) → one GLOBAL array sharded by ``spec`` over ``mesh``.
    Single-process this is just ``device_put``; multi-process it builds
    the global array from per-process local shards — what jit requires
    when the mesh spans processes (the torchrun-mode data path)."""
    arr = np.asarray(arr)
    sharding = NamedSharding(mesh, spec)
    if jax.process_count() == 1:
        return jax.device_put(arr, sharding)
    return jax.make_array_from_callback(arr.shape, sharding,
                                        lambda idx: arr[idx])


def process_local_put(arr, mesh: Mesh, spec: PartitionSpec) -> jax.Array:
    """Stage a host-identical batch as one GLOBAL array by handing JAX
    only this process's slice — ``jax.make_array_from_process_local_data``,
    the data path the torchrun contract implies: each worker materializes
    its own shard, never the full global batch on-device.

    Single-process (or a spec fully addressable from here) degrades to
    plain ``device_put``.  When this process's shards are not one
    contiguous block of the global array (e.g. a strided device order),
    falls back to :func:`host_to_global`'s per-shard callback, which
    handles any layout.
    """
    arr = np.asarray(arr)
    sharding = NamedSharding(mesh, spec)
    if jax.process_count() == 1 or sharding.is_fully_addressable:
        return jax.device_put(arr, sharding)
    idx_map = sharding.addressable_devices_indices_map(arr.shape)
    # bounding box of the local shards, per dimension
    lo = [d for d in arr.shape]
    hi = [0] * arr.ndim
    for idx in idx_map.values():
        for d, sl in enumerate(idx):
            start = 0 if sl.start is None else sl.start
            stop = arr.shape[d] if sl.stop is None else sl.stop
            lo[d] = min(lo[d], start)
            hi[d] = max(hi[d], stop)
    box = tuple(slice(a, b) for a, b in zip(lo, hi))
    uniq_bounds = {
        tuple(((0 if sl.start is None else sl.start),
               (arr.shape[d] if sl.stop is None else sl.stop))
              for d, sl in enumerate(idx))
        for idx in idx_map.values()}
    covered = sum(math.prod(b - a for a, b in bounds)
                  for bounds in uniq_bounds)
    if covered != math.prod(b - a for a, b in zip(lo, hi)):
        # local shards don't tile the box — non-contiguous layout
        return host_to_global(arr, mesh, spec)
    return jax.make_array_from_process_local_data(
        sharding, np.ascontiguousarray(arr[box]), arr.shape)


def local_scalar(x) -> float:
    """float() of a (replicated) result that works whether or not the
    array is fully addressable from this process."""
    if hasattr(x, "is_fully_addressable") and not x.is_fully_addressable:
        return float(np.asarray(x.addressable_data(0)))
    return float(x)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def sharded(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec(*spec))
