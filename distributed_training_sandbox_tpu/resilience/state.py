"""RunState: the strategy-agnostic unit of resumability.

A training run's restorable identity is more than its arrays.  Resuming
bit-exactly needs, beyond the (possibly sharded) params and optimizer
state, the *host-side* position of the run: which batch the loop would
consume next (``data_cursor``), the root PRNG key the seed produced
(``prng_key`` — a resume under a different ``--seed`` must fail loudly,
not silently fork the trajectory), the last completed step, and the loss
sequence so far (so a stitched run can report — and tests can pin — the
full concatenated series without replaying segment 1).

Array leaves travel through ``utils/checkpoint.py`` (Orbax: parallel
per-shard writes, reshard-on-restore when the mesh changed); the host
scalars or variable-length pieces (step, cursor, loss log, lineage) ride
in a ``runstate-<step>.json`` sidecar next to the Orbax step directory,
written after the save's host copy completes so the sidecar can never
describe data that was not yet captured.

:class:`Checkpointer` is the driver-facing policy object: ``--checkpoint
-every N`` saves are *asynchronous* and deferred to the step pump's next
sync point (``maybe_save(..., synced=...)``), so checkpointing rides the
existing host-sync schedule instead of adding blocking points; ``close()``
always waits for in-flight writes — the guarantee that a crash mid-write
never leaves a torn newest step (``tests/test_resilience.py``).
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from typing import Any

from ..utils import checkpoint as C

STATE_SCHEMA_VERSION = 1


class CheckpointCorruptError(RuntimeError):
    """A checkpoint step exists on disk but cannot be restored (torn
    write, truncation, bit rot).  The message is the CLI-facing contract:
    readable, names the step and directory, says what to do next."""


@dataclass
class RunState:
    """Everything one strategy run needs to resume bit-exactly.

    ``params``/``opt_state``/``prng_key`` are pytrees of (possibly
    sharded) arrays; the rest is host data.  ``step`` is the LAST
    COMPLETED step index; ``data_cursor`` counts host batches the loop
    has consumed (== step+1 for one-batch-per-step drivers, epochs for
    the pipeline driver) — the prefetcher may have pulled further ahead,
    which is exactly why the loop-side cursor is the thing saved."""

    params: Any
    opt_state: Any = None
    step: int = -1
    data_cursor: int = 0
    prng_key: Any = None
    loss_log: list = field(default_factory=list)
    lineage: dict = field(default_factory=dict)

    def array_tree(self) -> dict:
        """The Orbax-bound leaves (structure mirrored by ``_like_tree``)."""
        tree = {"params": self.params}
        if self.opt_state is not None:
            tree["opt"] = self.opt_state
        if self.prng_key is not None:
            tree["prng"] = self.prng_key
        return tree


def _meta_path(directory: str, step: int) -> str:
    return os.path.join(directory, f"runstate-{step}.json")


def _write_meta(directory: str, state: RunState,
                fingerprint: dict | None) -> None:
    meta = {
        "schema": STATE_SCHEMA_VERSION,
        "step": int(state.step),
        "data_cursor": int(state.data_cursor),
        "loss_log": [float(l) for l in state.loss_log],
        "lineage": state.lineage or {},
        "has_opt": state.opt_state is not None,
        "has_prng": state.prng_key is not None,
        "fingerprint": fingerprint or {},
    }
    path = _meta_path(directory, state.step)
    # every rank of a multi-process run writes this (identical) sidecar
    # into the shared directory: a per-process temp name keeps one
    # rank's replace from moving the file another is still writing
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "w") as f:
        json.dump(meta, f, indent=1)
        f.write("\n")
    os.replace(tmp, path)   # atomic: a reader sees old-or-new, never torn


def _globalize(tree):
    """Multi-process saves require every array leaf to be GLOBAL: Orbax
    refuses process-local arrays ("Cannot serialize host local arrays").
    Params and optimizer state come out of jit already global, but the
    PRNG root (``set_seed``'s single-device key) and any host-side numpy
    leaves are local to each process.  Their values are identical on
    every rank by construction (identically seeded), so replicating them
    over a mesh of ALL devices is value-preserving.  Single-process this
    is the identity."""
    import jax
    import numpy as np

    if jax.process_count() == 1:
        return tree
    from jax.sharding import Mesh, PartitionSpec
    from ..utils.mesh import host_to_global
    mesh = Mesh(np.asarray(jax.devices()), ("all",))

    def fix(leaf):
        if isinstance(leaf, jax.Array) and leaf.is_fully_addressable:
            return host_to_global(np.asarray(leaf), mesh, PartitionSpec())
        if isinstance(leaf, np.ndarray):
            return host_to_global(leaf, mesh, PartitionSpec())
        return leaf

    return jax.tree.map(fix, tree)


def _localize(restored, like):
    """Inverse of :func:`_globalize` on the restore path: leaves the
    caller's ``like`` holds process-locally (the PRNG key) come back
    from a globalized checkpoint as non-addressable global arrays —
    fold each back to the local replica so downstream code sees the
    same shape of array it handed in."""
    import jax
    import numpy as np

    def fix(r, l):
        if isinstance(l, jax.Array) and l.is_fully_addressable \
                and isinstance(r, jax.Array) \
                and not r.is_fully_addressable:
            return jax.device_put(np.asarray(r.addressable_data(0)),
                                  l.sharding)
        return r

    return jax.tree.map(fix, restored, like)


def save_run_state(mgr, state: RunState, *, wait: bool = False,
                   fingerprint: dict | None = None) -> None:
    """Save ``state`` under its step.  ``wait=False`` leaves the disk
    write async (the device->host copy inside Orbax is synchronous, so
    the next train step may donate/overwrite the buffers immediately);
    the sidecar is written right after — by then the data is captured."""
    C.save_state(mgr, state.step, _globalize(state.array_tree()),
                 wait=wait)
    _write_meta(os.fspath(mgr.directory), state, fingerprint)


def _read_meta(directory: str, step: int) -> dict | None:
    try:
        with open(_meta_path(directory, step)) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return None


def _match_commitment(restored, like):
    """Orbax restores every leaf COMMITTED to ``like``'s sharding — but
    optimizer trees routinely carry uncommitted host scalars (Adam's
    ``count``), and a scalar pinned to device 0 next to mesh-sharded
    params is a "incompatible devices" jit error on the very next step.
    Leaves that were uncommitted in ``like`` are returned uncommitted."""
    import jax
    import numpy as np

    def fix(r, l):
        if isinstance(l, jax.Array) and not getattr(l, "_committed", True):
            return jax.device_put(np.asarray(r))
        return r

    return jax.tree.map(fix, restored, like)


def restore_run_state(mgr, *, like: RunState,
                      step: int | None = None) -> RunState:
    """Restore the newest (or given) step into ``like``'s structure and
    shardings (resharding if ``like`` lives on a different mesh than the
    one that saved).  A torn or corrupted step raises
    :class:`CheckpointCorruptError` with a readable message, not a raw
    tensorstore traceback."""
    directory = os.fspath(mgr.directory)
    if step is None:
        step = C.latest_step(mgr)
        if step is None:
            raise FileNotFoundError(f"no checkpoint steps in {directory}")
    meta = _read_meta(directory, step) or {}
    tree = {"params": like.params}
    if meta.get("has_opt", like.opt_state is not None) \
            and like.opt_state is not None:
        tree["opt"] = like.opt_state
    if meta.get("has_prng", like.prng_key is not None) \
            and like.prng_key is not None:
        tree["prng"] = like.prng_key
    try:
        restored = _localize(C.restore_state(mgr, like=_globalize(tree),
                                             step=step), tree)
        restored = _match_commitment(restored, tree)
    except CheckpointCorruptError:
        raise
    except Exception as e:  # noqa: BLE001 - rewrapped with context
        raise CheckpointCorruptError(
            f"failed to restore step {step} from {directory}: "
            f"{type(e).__name__}: {str(e).splitlines()[0] if str(e) else e}"
            f" — the checkpoint is torn or corrupted; delete "
            f"{os.path.join(directory, str(step))} to fall back to an "
            f"earlier step, or restart without --resume") from e
    return RunState(
        params=restored["params"],
        opt_state=restored.get("opt"),
        prng_key=restored.get("prng"),
        step=int(meta.get("step", step)),
        data_cursor=int(meta.get("data_cursor", step + 1)),
        loss_log=list(meta.get("loss_log", [])),
        lineage=dict(meta.get("lineage", {})),
    )


class Checkpointer:
    """Policy + lifecycle around one run's checkpoint directory.

    ``maybe_save`` marks a step due every ``every`` steps but only
    writes at the next pump sync point (``synced=True``), asynchronously;
    ``save`` is the unconditional form; ``close()`` waits for in-flight
    writes on EVERY exit path (the supervisor calls it from a finally),
    so an async save can never be torn by process exit — the hazard
    ``save_state(..., wait=False)`` callers had before this class."""

    def __init__(self, directory, *, every: int = 0, keep: int = 3,
                 fingerprint: dict | None = None):
        self.directory = os.path.abspath(os.fspath(directory))
        self.every = max(int(every), 0)
        self.keep = keep
        self.fingerprint = dict(fingerprint or {})
        self._mgr = None
        self._due = False
        self._saved_steps: set[int] = set()
        # host-phase span stream (telemetry.spans.SpanStream): drivers
        # with a TelemetryRun assign it so each save's blocking portion
        # shows up as a checkpoint/save span on the merged timeline
        self.spans = None
        # live MetricsRegistry, same late-assignment pattern
        # (``ckpt.metrics = telem.metrics``); feeds are None-tolerant
        self.metrics = None

    @property
    def mgr(self):
        if self._mgr is None:
            self._mgr = C.checkpoint_manager(self.directory,
                                             max_to_keep=self.keep)
        return self._mgr

    # ---- restore --------------------------------------------------------
    def restore_latest(self, like: RunState) -> RunState | None:
        """Latest *intact* RunState, or None when the directory holds no
        steps (a resume of a run that never reached its first save
        starts fresh).  Verifies the saved fingerprint (seed/precision/
        batch) against this run's — a silently different config must not
        wear a restored trajectory.  A torn or corrupted newest step
        (the shape a SIGKILL mid-async-save leaves behind) is SKIPPED
        with a warning and the previous intact step restored instead —
        an elastic resume after a torn save self-heals; only when every
        step is corrupt does the error propagate."""
        if not os.path.isdir(self.directory):
            return None
        self.mgr.wait_until_finished()
        steps = sorted(self.mgr.all_steps() or [], reverse=True)
        if not steps:
            return None
        last_err: CheckpointCorruptError | None = None
        for step in steps:
            meta = _read_meta(self.directory, step) or {}
            saved_fp = meta.get("fingerprint") or {}
            for k, want in self.fingerprint.items():
                have = saved_fp.get(k)
                if have is not None and want is not None and have != want:
                    raise SystemExit(
                        f"cannot resume from {self.directory}: checkpoint "
                        f"was written with {k}={have!r}, this run has "
                        f"{k}={want!r} — resuming would silently fork the "
                        f"trajectory (rerun with the original {k}, or a "
                        f"fresh --checkpoint-dir)")
            try:
                state = restore_run_state(self.mgr, like=like, step=step)
            except CheckpointCorruptError as e:
                print(f"[resilience] WARNING: checkpoint step {step} in "
                      f"{self.directory} is torn or corrupt — skipping it"
                      f" and falling back to the previous intact step")
                last_err = e
                continue
            if like.prng_key is not None and state.prng_key is not None:
                import numpy as np
                if not np.array_equal(np.asarray(like.prng_key),
                                      np.asarray(state.prng_key)):
                    raise SystemExit(
                        f"cannot resume from {self.directory}: the "
                        f"checkpointed PRNG root key differs from this "
                        f"run's (different --seed?) — the resumed data/"
                        f"init stream would not match the original run")
            self._saved_steps.add(step)
            return state
        raise last_err

    # ---- save policy ----------------------------------------------------
    def maybe_save(self, i: int, state_fn, *, synced: bool) -> bool:
        """Call once per completed step ``i``.  Marks a save due every
        ``every`` steps; performs it (async) at the first due step where
        the pump has synced — all losses <= i are then resolved, so the
        saved ``loss_log`` is complete and the device is quiesced enough
        that the host copy does not race dispatch."""
        if self.every and (i + 1) % self.every == 0:
            self._due = True
        if self._due and synced:
            self.save(state_fn(), wait=False)
            self._due = False
            return True
        return False

    def save(self, state: RunState, *, wait: bool = False) -> None:
        if state.step in self._saved_steps:
            return
        from ..telemetry.spans import maybe_span
        state.lineage.setdefault("fingerprint", {}).update(self.fingerprint)
        with maybe_span(self.spans, "checkpoint/save", cat="checkpoint",
                        step=int(state.step), wait=bool(wait)):
            save_run_state(self.mgr, state, wait=wait,
                           fingerprint=self.fingerprint)
        from ..telemetry.metrics import maybe_inc
        maybe_inc(self.metrics, "checkpoint_saves_total")
        self._saved_steps.add(state.step)
        self._prune_meta()

    def save_final(self, state: RunState) -> None:
        """The exit/preemption save: unconditional, then waits — the
        step the next segment resumes from must be fully committed
        before this process exits."""
        self.save(state, wait=True)

    def _prune_meta(self) -> None:
        """Drop sidecars for steps Orbax's max_to_keep already pruned."""
        try:
            live = set(self.mgr.all_steps())
            for name in os.listdir(self.directory):
                if name.startswith("runstate-") and name.endswith(".json"):
                    step = int(name[len("runstate-"):-len(".json")])
                    if step not in live:
                        os.unlink(os.path.join(self.directory, name))
        except (OSError, ValueError):
            pass

    # ---- lifecycle ------------------------------------------------------
    def close(self) -> None:
        """Wait for in-flight async writes.  Idempotent; the supervisor
        runs this in a ``finally`` so even a crashing attempt cannot
        leave a half-committed newest step behind."""
        if self._mgr is not None:
            self._mgr.wait_until_finished()

    def __enter__(self) -> "Checkpointer":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False
