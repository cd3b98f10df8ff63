"""Declarative knob space: the axes a hand-written knob matrix
enumerates, as data.

A :class:`TunerCandidate` is one point — a superset of the memory
planner's :class:`~..memory_plan.planner.Candidate` (which covers the
per-step knobs) extended with the driver-level knobs the planner never
sees: batch scale, the overlap engine mode, sync cadence, and DDP bucket
size.  A :class:`KnobSpace` is a cross product of named axes with the
same feasibility rules the step factories enforce, so enumeration never
emits a candidate the drivers would reject.
"""

from __future__ import annotations

import hashlib
import json
import random
from dataclasses import asdict, dataclass

from ..memory_plan.planner import REMAT_POLICIES


def mesh_feasible(shape, *, n_devices=None, n_heads=None,
                  n_kv_heads=None, seq_len=None) -> bool:
    """Enumeration-time feasibility of one ``mesh_shape`` tuple
    (dp, fsdp, tp[, sp]): the axis product must equal the device count,
    tp must divide both head counts, sp must divide the sequence length.
    Mirrors ``parallel.composable.plan_feasible`` without importing the
    jax-side machinery (the two are pinned equal by
    tests/test_composable.py).  Unknown context (None) never prunes."""
    dp, fsdp, tp, sp = (tuple(shape) + (1, 1, 1, 1))[:4]
    if min(dp, fsdp, tp, sp) < 1:
        return False
    if n_devices is not None and dp * fsdp * tp * sp != n_devices:
        return False
    if tp > 1:
        for heads in (n_heads, n_kv_heads):
            if heads is not None and heads % tp:
                return False
    if sp > 1 and seq_len is not None and seq_len % sp:
        return False
    return True


@dataclass(frozen=True)
class TunerCandidate:
    """One point of the tuner's knob space."""
    strategy: str = "fsdp"
    batch_scale: int = 1
    accum_steps: int = 1
    remat_policy: str = "full"
    matmul_precision: str = "bf16"
    state_precision: str = "full"
    offload: str = "none"
    overlap: str = "none"   # "none"|"ring"|"ring_fused"|"ring_fused_pallas"
    sync_every: int = 0            # 0 = pump default (no per-step sync)
    bucket_mb: float | None = None  # DDP-family bucket size
    mesh_shape: tuple | None = None  # (dp, fsdp, tp[, sp]); None = flat dp

    # ------------------------------------------------------------ names
    def bench_name(self) -> str:
        """The bench-prior row name for this candidate, in the grammar
        ``parse_bench_config_name`` reads back (explicit[_remat]
        [_int8_bwd|_fp8(_delayed|_pallas)][_s8][_b{N}x]).  Knobs the
        bench grammar has no token for
        (accum, offload, overlap, sync) get trailing tags — such names
        parse to None, which is correct: no measured bench row covers
        them."""
        parts = ["explicit"]
        if self.remat_policy != "full":
            parts.append(self.remat_policy)
        if self.matmul_precision == "int8_bwd":
            parts.append("int8_bwd")
        elif self.matmul_precision.startswith("fp8"):
            parts.append(self.matmul_precision)
        if self.state_precision == "int8":
            parts.append("s8")
        if self.batch_scale > 1:
            parts.append(f"b{self.batch_scale}x")
        if self.accum_steps > 1:
            parts.append(f"accum{self.accum_steps}")
        if self.offload != "none":
            parts.append(f"offload_{self.offload}")
        if self.overlap != "none":
            parts.append(self.overlap)
        if self.sync_every:
            parts.append(f"sync{self.sync_every}")
        if self.mesh_shape:
            # "_mesh2x2x2" — parse_bench_config_name reads this back
            parts.append("mesh" + "x".join(str(s)
                                           for s in self.mesh_shape))
        return "_".join(parts)

    def label(self) -> str:
        return self.bench_name()

    # -------------------------------------------------- driver adapters
    def cfg_overrides(self) -> dict:
        """``TransformerConfig`` overrides (``dataclasses.replace``)."""
        over = {"remat_policy": self.remat_policy,
                "matmul_precision": self.matmul_precision}
        if self.offload == "opt_act":
            over["offload_activations"] = True
        return over

    def step_kwargs(self) -> dict:
        """``fsdp.make_fsdp_train_step`` kwargs for this candidate."""
        kw: dict = {"reshard_after_forward": True}
        if self.accum_steps > 1:
            kw["accum_steps"] = self.accum_steps
        if self.state_precision != "full":
            kw["state_precision"] = self.state_precision
        if self.offload != "none":
            kw["offload"] = self.offload
        if self.overlap != "none":
            kw["overlap"] = self.overlap
        return kw

    def to_dict(self) -> dict:
        return asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "TunerCandidate":
        kw = {k: d[k] for k in cls.__dataclass_fields__ if k in d}
        if kw.get("mesh_shape") is not None:
            # plan.json round trip: JSON has no tuples
            kw["mesh_shape"] = tuple(int(s) for s in kw["mesh_shape"])
        return cls(**kw)


# default axes: the envelope of every hand-written KNOB_MATRIX row plus
# the planner-only knobs (accum, offload) the matrix never swept
_DEFAULT_AXES = dict(
    strategy=("fsdp",),
    batch_scale=(1, 2, 4, 8),
    accum_steps=(1, 2),
    remat_policy=REMAT_POLICIES,
    matmul_precision=("bf16", "int8_bwd", "fp8", "fp8_delayed",
                      "fp8_pallas"),
    state_precision=("full", "int8"),
    offload=("none", "opt"),
    overlap=("none",),
    sync_every=(0,),
    bucket_mb=(None,),
    # None = the flat-dp fsdp mesh; tuples are (dp, fsdp, tp) composable
    # plans — the combinatorial axis the composable driver executes.
    # Infeasible shapes (axis product != device count, tp not dividing
    # the head counts) are dropped at enumeration when the context is
    # known; the analytic waterline prunes the over-budget rest.
    mesh_shape=(None, (2, 2, 2), (1, 2, 4), (1, 4, 2)),
)


@dataclass(frozen=True)
class KnobSpace:
    """Cross product of knob axes with the step factories' feasibility
    rules applied at enumeration time.  Frozen + tuple-valued so the
    space itself is hashable content: :meth:`space_hash` is the
    provenance stamp a ``plan.json`` carries."""
    strategy: tuple = _DEFAULT_AXES["strategy"]
    batch_scale: tuple = _DEFAULT_AXES["batch_scale"]
    accum_steps: tuple = _DEFAULT_AXES["accum_steps"]
    remat_policy: tuple = _DEFAULT_AXES["remat_policy"]
    matmul_precision: tuple = _DEFAULT_AXES["matmul_precision"]
    state_precision: tuple = _DEFAULT_AXES["state_precision"]
    offload: tuple = _DEFAULT_AXES["offload"]
    overlap: tuple = _DEFAULT_AXES["overlap"]
    sync_every: tuple = _DEFAULT_AXES["sync_every"]
    bucket_mb: tuple = _DEFAULT_AXES["bucket_mb"]
    mesh_shape: tuple = _DEFAULT_AXES["mesh_shape"]

    def axes(self) -> dict:
        return {k: list(getattr(self, k))
                for k in _DEFAULT_AXES}

    def space_hash(self) -> str:
        """sha256 over the canonical JSON of the axes — two spaces with
        the same axes hash identically regardless of construction."""
        blob = json.dumps(self.axes(), sort_keys=True, default=str)
        return hashlib.sha256(blob.encode()).hexdigest()[:16]

    def enumerate(self, per_device_batch: int, *,
                  n_devices: int | None = None,
                  n_heads: int | None = None,
                  n_kv_heads: int | None = None,
                  seq_len: int | None = None) -> list[TunerCandidate]:
        """Every feasible candidate, in a deterministic (sorted-axes
        cross-product) order.  Feasibility = the step factories' own
        rules: accumulation must divide the per-device batch at that
        candidate's scale; activation offload needs a named-save remat
        policy (same rule as ``memory_plan.enumerate_candidates``); a
        mesh shape must pass :func:`mesh_feasible` under whatever device
        /head/sequence context the caller knows (None never prunes)."""
        out = []
        mesh_shapes = [ms for ms in self.mesh_shape
                       if ms is None or mesh_feasible(
                           ms, n_devices=n_devices, n_heads=n_heads,
                           n_kv_heads=n_kv_heads, seq_len=seq_len)]
        for bs in self.batch_scale:
            pdb = max(per_device_batch, 1) * bs
            for strat in self.strategy:
                for a in self.accum_steps:
                    if a < 1 or (pdb % a):
                        continue
                    for r in self.remat_policy:
                        for q in self.matmul_precision:
                            for s in self.state_precision:
                                for o in self.offload:
                                    if o == "opt_act" and r not in (
                                            "save_attn", "save_dots_q8"):
                                        continue
                                    for ov in self.overlap:
                                        for se in self.sync_every:
                                            for bm in self.bucket_mb:
                                                for ms in mesh_shapes:
                                                    if ms is not None \
                                                            and (s != "full"
                                                                 or o != "none"):
                                                        # the composable
                                                        # step composes
                                                        # accum/overlap
                                                        # only — int8
                                                        # state and
                                                        # offload are
                                                        # flat-dp fsdp
                                                        # knobs
                                                        continue
                                                    out.append(
                                                        TunerCandidate(
                                                            strat, bs, a,
                                                            r, q, s, o,
                                                            ov, se, bm,
                                                            ms))
        return out

    def sample(self, n: int, seed: int,
               per_device_batch: int = 1) -> list[TunerCandidate]:
        """Deterministic sample of the feasible space — the same seed
        yields the same candidates on every host/run."""
        cands = self.enumerate(per_device_batch)
        if n >= len(cands):
            return cands
        return random.Random(seed).sample(cands, n)

    @classmethod
    def from_axes(cls, axes: dict) -> "KnobSpace":
        def _axis(k, v):
            if k == "mesh_shape":
                # JSON round trip: inner lists -> tuples so candidates
                # and hashes compare equal regardless of provenance
                return tuple(None if s is None else tuple(s) for s in v)
            return tuple(v)
        kw = {k: _axis(k, v) for k, v in axes.items()
              if k in _DEFAULT_AXES}
        return cls(**kw)


#: the ServingKnobSpace axis names, in canonical order — axes(),
#: space_hash(), enumerate() and from_axes() all key off this one tuple
_SERVING_AXES = ("max_batch", "page_size", "prefill_chunk",
                 "sync_every", "spec_k", "draft_layers")


@dataclass(frozen=True)
class ServingKnobSpace:
    """The serving-pool half of the knob space (objective = p99
    latency): the ``ServingEngine`` pool knobs ``serve_bench.py``
    exposes as flags, plus the speculative-decoding axes (``spec_k`` =
    draft proposal length, 0 = off; ``draft_layers`` = depth of the
    truncated-target draft model — the draft-model choice axis)."""
    max_batch: tuple = (2, 4, 8)
    page_size: tuple = (4, 8, 16)
    prefill_chunk: tuple = (8, 16, 32)
    sync_every: tuple = (2, 4, 8)
    spec_k: tuple = (0, 2, 4)
    draft_layers: tuple = (1, 2)

    def axes(self) -> dict:
        return {k: list(getattr(self, k)) for k in _SERVING_AXES}

    def space_hash(self) -> str:
        blob = json.dumps(self.axes(), sort_keys=True)
        return hashlib.sha256(blob.encode()).hexdigest()[:16]

    def enumerate(self) -> list[dict]:
        out = []
        for mb in self.max_batch:
            for ps in self.page_size:
                for pc in self.prefill_chunk:
                    for se in self.sync_every:
                        for sk in self.spec_k:
                            # draft_layers only varies a live draft:
                            # spec_k=0 pins it to the first value so
                            # vanilla decode isn't enumerated twice
                            dls = (self.draft_layers if sk
                                   else self.draft_layers[:1])
                            for dl in dls:
                                out.append({
                                    "max_batch": mb, "page_size": ps,
                                    "prefill_chunk": pc,
                                    "sync_every": se, "spec_k": sk,
                                    "draft_layers": dl})
        return out

    @classmethod
    def from_axes(cls, axes: dict) -> "ServingKnobSpace":
        kw = {k: tuple(v) for k, v in axes.items()
              if k in _SERVING_AXES}
        return cls(**kw)
