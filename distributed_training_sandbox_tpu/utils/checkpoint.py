"""Checkpoint / resume of sharded train state (Orbax-backed).

The reference has NO checkpointing — every run is random-init
(SURVEY.md §5.4: "no state_dict save/load anywhere"; models rebuilt from
config at ``fsdp/train_fsdp.py:61-64``).  A framework a reference user
switches to needs one, and Orbax is the idiomatic TPU choice: it writes
each device's shards in parallel (OCDBT/tensorstore), restores directly
into the requested ``NamedSharding`` layout — resharding on restore if
the mesh changed — and is async-capable for multi-host.

Surface (three calls, train-loop friendly):

    mgr = checkpoint_manager(dir, max_to_keep=3)
    save_state(mgr, step, {"params": shards, "opt": opt_state})
    state = restore_state(mgr, like={"params": shards, "opt": opt_state})

``like`` supplies the tree structure + shapes + shardings to restore
into (typically freshly-initialized state); restore is exact — resuming
mid-run reproduces the unbroken trajectory bit-for-bit, which the test
suite pins.
"""

from __future__ import annotations

import contextlib
import importlib
import os
from typing import Any

import jax


def _ocp():
    """Deferred orbax import — keeps ``utils`` import light for the many
    paths that never checkpoint."""
    return importlib.import_module("orbax.checkpoint")


def checkpoint_manager(directory: str | os.PathLike, *,
                       max_to_keep: int = 3) -> "ocp.CheckpointManager":
    """A step-indexed manager (keeps the newest ``max_to_keep`` steps)."""
    ocp = _ocp()
    options = ocp.CheckpointManagerOptions(max_to_keep=max_to_keep,
                                           create=True)
    return ocp.CheckpointManager(os.path.abspath(os.fspath(directory)),
                                 options=options)


def save_state(mgr: "ocp.CheckpointManager", step: int, state: Any,
               *, wait: bool = True) -> None:
    """Save a pytree of (possibly sharded) arrays under ``step``.
    ``wait=False`` leaves the write async (overlap with the next train
    steps); call ``mgr.wait_until_finished()`` before exiting."""
    mgr.save(step, args=_ocp().args.PyTreeSave(state))
    if wait:
        mgr.wait_until_finished()


@contextlib.contextmanager
def closing(mgr: "ocp.CheckpointManager"):
    """Guarantee ``wait_until_finished()`` on EVERY exit path — the
    async-save safety contract.  ``save_state(..., wait=False)`` lets the
    disk write overlap the next train steps, but a crash (or plain
    return) before the write commits would leave a torn newest step;
    wrapping the manager's lifetime in ``closing`` makes that impossible:

        with closing(checkpoint_manager(dir)) as mgr:
            save_state(mgr, step, state, wait=False)
            ...                     # crash here still waits the write out
    """
    try:
        yield mgr
    finally:
        mgr.wait_until_finished()


def latest_step(mgr: "ocp.CheckpointManager") -> int | None:
    return mgr.latest_step()


def restore_state(mgr: "ocp.CheckpointManager", *, like: Any,
                  step: int | None = None) -> Any:
    """Restore the newest (or given) step into ``like``'s structure,
    dtypes, and shardings — placement happens during restore, so a
    dp-sharded param tree comes back dp-sharded without a host round
    trip (and reshards automatically if ``like``'s mesh differs from
    the one that saved).

    ``strict=False``: a leaf whose stored shape differs from ``like``'s is
    truncated or zero-padded to fit.  ZeRO's flat shards are padded to a
    multiple of the SAVING world size (``parallel.zero._pad_flat``), so
    the same state is (104,) from eight devices and (100,) on four; the
    difference is always trailing zeros.  Which model a checkpoint
    belongs to is the run fingerprint's check
    (``resilience.state.Checkpointer.restore_latest``), not this one's."""
    if step is None:
        step = mgr.latest_step()
        if step is None:
            raise FileNotFoundError(
                f"no checkpoint found under {mgr.directory}")
    ocp = _ocp()
    abstract = jax.tree.map(ocp.utils.to_shape_dtype_struct, like)
    restore_args = jax.tree.map(
        lambda a: ocp.ArrayRestoreArgs(
            sharding=a.sharding, global_shape=a.shape, dtype=a.dtype,
            strict=False), abstract)
    return mgr.restore(step, args=ocp.args.PyTreeRestore(
        item=abstract, restore_args=restore_args))


def restore_params(ckpt_dir, params, *, tag: str = "restore"):
    """THE restore-and-report path the eval/demo scripts share: open
    ``ckpt_dir``, restore the newest step's ``{"params": ...}`` into
    ``params``' structure and shardings, print the one-line
    "restored step N from DIR" contract under ``tag``'s prefix, and
    return ``(restored_params, step)``.  Raises SystemExit with a
    readable message when the directory holds no steps."""
    mgr = checkpoint_manager(ckpt_dir)
    step = latest_step(mgr)
    if step is None:
        raise SystemExit(f"no checkpoint steps in {ckpt_dir}")
    state = restore_state(mgr, like={"params": params})
    print(f"[{tag}] restored step {step} from {ckpt_dir}")
    return state["params"], step
