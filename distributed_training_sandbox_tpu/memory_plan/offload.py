"""Contracted host offload: optimizer state (and named remat activations)
parked in host memory, streamed over PCIe around the step.

The mechanism is JAX memory kinds: a leaf placed under a
``NamedSharding(..., memory_kind="pinned_host")`` lives in host DRAM; a
``jax.device_put`` to the ``"device"`` kind *inside* a jitted step lowers
to a ``MoveToDevice`` custom call (and back, ``MoveToHost``) that XLA's
latency-hiding scheduler can overlap with compute.  ``analysis/hlo_lint``
used to classify every such custom call as a hot-path violation; with the
:class:`OffloadPlan` below the transfers become *declared* — the lint
count-checks them instead (see ``hlo_lint.check_host_transfers``).

Backends whose only memory IS host memory (the 8-way CPU CI mesh)
degrade to an identity placement — the
step is bitwise-identical to no-offload, the plan records
``supported=False`` and declares zero transfers, and the contract lint
then *forbids* transfer custom calls, so the fallback is still checked.
"""

from __future__ import annotations

from dataclasses import dataclass, field

OFFLOAD_MODES = ("none", "opt", "opt_act")
HOST_KIND = "pinned_host"
DEVICE_KIND = "device"

# Checkpoint names offloadable per remat policy (the policies that save
# *named* tensors — the only ones save_and_offload_only_these_names can
# redirect to host).
OFFLOADABLE_REMAT_NAMES = {
    "save_attn": ("attn_out",),
    "save_dots_q8": ("dot_q8",),
}


def supports_host_offload(device=None) -> bool:
    """True when the backend has a ``pinned_host`` memory space DISTINCT
    from device memory (TPU).  The CPU backend lists ``pinned_host`` too,
    but every kind it lists is the same host DRAM and XLA compiles the
    transfers away — an offload plan there declares none."""
    import jax
    device = device or jax.devices()[0]
    if device.platform == "cpu":
        return False
    return HOST_KIND in {m.kind for m in device.addressable_memories()}


@dataclass(frozen=True)
class OffloadPlan:
    """What one step's host-offload choreography is *declared* to do —
    produced by :func:`plan_offload` at step-build time, recorded into
    ``ContractContext.extra["offload"]`` so the contract lint can expect
    exactly these transfers and reject any others."""
    mode: str = "none"              # none | opt | opt_act
    supported: bool = False         # backend has a pinned_host space
    n_state_leaves: int = 0         # optimizer-state leaves parked on host
    state_bytes: int = 0            # bytes per direction per step (opt)
    act_names: tuple = field(default_factory=tuple)  # offloaded ckpt names

    def host_transfer_counts(self) -> dict:
        """Declared ``MoveToHost``/``MoveToDevice`` custom-call count
        ranges for the compiled step.  Site counts are ranges, not exact:
        XLA may fuse per-leaf moves or split them per shard, and the
        activation moves repeat per saved name — but zero transfers when
        offload is active (the annotation silently dropped) and any
        transfer when it is not are both violations."""
        if not (self.supported and self.mode != "none"):
            return {}
        n = self.n_state_leaves
        hi = 2 * n + 8 * len(self.act_names)
        return {"move_to_host": (1, max(hi, 1)),
                "move_to_device": (1, max(hi, 1))}

    def to_dict(self) -> dict:
        return {"mode": self.mode, "supported": self.supported,
                "n_state_leaves": self.n_state_leaves,
                "state_bytes": self.state_bytes,
                "act_names": list(self.act_names)}


def plan_offload(mode: str, opt_state=None, *, act_names=(),
                 supported: bool | None = None) -> OffloadPlan:
    """Declare the offload choreography for one step build.  ``opt_state``
    is the optimizer-state tree whose array leaves get parked on host
    (mode "opt"/"opt_act"); ``act_names`` the remat checkpoint names
    redirected to host (mode "opt_act")."""
    if mode not in OFFLOAD_MODES:
        raise ValueError(f"offload={mode!r}; choose from {OFFLOAD_MODES}")
    if supported is None:
        supported = supports_host_offload()
    if mode == "none":
        return OffloadPlan()
    import jax
    from ..utils.memory import tree_size_bytes
    leaves = [l for l in jax.tree.leaves(opt_state)
              if hasattr(l, "shape") and getattr(l, "ndim", 0) > 0]
    return OffloadPlan(
        mode=mode, supported=supported, n_state_leaves=len(leaves),
        state_bytes=tree_size_bytes(opt_state) if opt_state is not None
        else 0,
        act_names=tuple(act_names) if mode == "opt_act" else ())


def _retarget(leaf, kind: str):
    """The leaf's own sharding with its memory kind swapped — keeps the
    partition spec (and mesh) exactly as the strategy placed it."""
    import jax
    sh = getattr(leaf, "sharding", None)
    if sh is None or not hasattr(sh, "with_memory_kind"):
        return None
    return sh.with_memory_kind(kind)


def offload_tree(tree, kind: str = HOST_KIND):
    """``device_put`` every array leaf of ``tree`` into the ``kind``
    memory space, preserving each leaf's partition spec.  Outside jit
    this is the at-rest placement (park the Adam moments on host between
    steps); scalar/unsharded leaves pass through untouched."""
    import jax

    def put(l):
        target = _retarget(l, kind)
        if target is None or getattr(l, "ndim", 0) == 0:
            return l
        return jax.device_put(l, target)

    return jax.tree.map(put, tree)


def stream_tree(tree, kind: str):
    """The *in-jit* transfer: ``device_put`` each leaf toward ``kind``
    memory, lowering to MoveToDevice/MoveToHost custom calls the
    scheduler can hide.  Identity on scalars (the Adam step counter
    stays wherever jit wants it)."""
    import jax
    space = {HOST_KIND: jax.memory.Space.Host,
             DEVICE_KIND: jax.memory.Space.Device}[kind]

    def put(l):
        if getattr(l, "ndim", 0) == 0:
            return l
        return jax.device_put(l, space)

    return jax.tree.map(put, tree)
