"""Fully-sharded data parallel training of the real transformer LM.

Twin of the reference's FSDP2 path (``fsdp/train_fsdp.py:78-97``): every
parameter sharded at rest, per-decoder-layer all-gather around compute,
gradients reduce-scattered back to shards, optimizer stepping on shards
(created *after* sharding in the reference — here the optimizer state is
simply built with the same sharding as the params).

Two variants, mirroring the course's from-scratch-then-library rule:

  * **explicit** (`make_fsdp_train_step`): shard_map with hand-placed
    collectives.  Per-layer params are gathered *inside* the rematerialized
    ``lax.scan`` body (``models.transformer.forward``'s ``layer_hook``
    seam), so the backward pass re-gathers them — exactly
    ``reshard_after_forward=True`` (ZeRO-3, reference
    ``train_fsdp.py:84-85``).  With ``reshard_after_forward=False`` the
    gather happens once before the scan and the gathered params stay live
    through the backward (ZeRO-2, ``train_fsdp.py:86``).  Gradients need no
    separate choreography: they flow through the all_gather's AD transpose,
    which IS a psum_scatter — the backward reduce-scatter of FSDP, one per
    gathered leaf, summed across the dp axis.
  * **auto** (`make_fsdp_auto_train_step`): jit with NamedSharding
    constraints only — XLA chooses the collective schedule.  The analogue of
    using torch's ``fully_shard`` after hand-rolling ZeRO.

Sharding layout (`fsdp_specs`): stacked layer leaves (L, a, b) shard their
*first non-layer* dim; plain leaves (embedding, final norm) shard dim 0.
All-gathers are then contiguous row gathers, and every hot matmul sees full
(in, out) operands on the MXU.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import transformer as T
from ..ops import collectives as C
from ..utils.profiling import scope
from . import optim


def _spec_map(f, tree, specs, *rest):
    """tree.map over (leaf, spec) pairs — PartitionSpec is itself a leaf."""
    return jax.tree.map(f, tree, specs, *rest,
                        is_leaf=lambda x: isinstance(x, P))


# ------------------------------------------------------------------ layout

def fsdp_specs(params, axis: str = "dp") -> dict:
    """PartitionSpec tree: shard dim 0 of plain leaves, dim 1 of stacked
    (L, ...) layer leaves (dim 0 is the scan/layer dim)."""

    def leaf_spec(path, leaf):
        inside_layers = any(getattr(k, "key", None) == "layers"
                            for k in path)
        if inside_layers:
            return P(None, axis)
        return P(axis)

    return jax.tree_util.tree_map_with_path(leaf_spec, params)


def check_divisibility(params, specs, mesh: Mesh) -> None:
    def chk(path, leaf, spec):
        for dim, name in enumerate(spec):
            if name is None:
                continue
            ws = int(mesh.shape[name])
            if leaf.shape[dim] % ws:
                raise ValueError(
                    f"param {jax.tree_util.keystr(path)} dim {dim} of size "
                    f"{leaf.shape[dim]} not divisible by mesh axis "
                    f"{name!r}={ws}")
    jax.tree_util.tree_map_with_path(chk, params, specs)


def shard_params_fsdp(params, mesh: Mesh, axis: str = "dp"):
    """Move (replicated/host) params to their at-rest FSDP sharding — the
    ``fully_shard(module)`` moment (reference ``train_fsdp.py:90-94``)."""
    specs = fsdp_specs(params, axis)
    check_divisibility(params, specs, mesh)
    return _spec_map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)),
        params, specs)


def _q8_scale_spec(spec: P, ndim: int) -> P:
    """The Q8 scale leaf's spec: the param's spec with its LAST dim
    unsharded (the scale's last dim is 1)."""
    entries = list(spec) + [None] * (ndim - len(spec))
    entries[ndim - 1] = None
    return P(*entries)


def q8_state_specs(params_sharded, specs):
    """PartitionSpec tree matching ``optim8.adam8_init``'s state: Q8
    leaves for ndim ≥ 2 params, plain specs for 1-D ones."""
    from .optim8 import Q8

    def leaf(p, s):
        if p.ndim < 2:
            return s
        return Q8(q=s, scale=_q8_scale_spec(s, p.ndim))

    return _spec_map(leaf, params_sharded, specs)


def init_fsdp_opt_state8(params_sharded, axis: str = "dp"):
    """int8-at-rest Adam moments (``parallel.optim8``) sharded like the
    params — cuts the largest resident block (mu/nu, 3.31 GB of the
    flagship's 4.96 GB at rest, ``scripts/memory_waterline.py``) to
    ~half.  ``axis``
    must match the FSDP axis the params were sharded over."""
    from . import optim8

    state = optim8.adam8_init(params_sharded)
    specs = fsdp_specs(params_sharded, axis)
    sspecs = q8_state_specs(params_sharded, specs)
    leaf = jax.tree.leaves(params_sharded)[0]
    if not isinstance(getattr(leaf, "sharding", None), NamedSharding):
        return state
    mesh = leaf.sharding.mesh
    put = lambda x, s: jax.device_put(x, NamedSharding(mesh, s))
    placed = jax.tree.map(
        lambda x, s: put(x, s), (state.mu, state.nu), (sspecs, sspecs),
        is_leaf=lambda x: isinstance(x, P))
    return optim.AdamState(
        mu=placed[0], nu=placed[1],
        count=jax.device_put(state.count, NamedSharding(mesh, P())))


def init_fsdp_opt_state(params_sharded, state_dtype=None):
    """Adam state with the same sharding as the param shards it tracks —
    optimizer-after-sharding (reference ``train_fsdp.py:96-97``).  The
    reference's bf16 model gives bf16 torch AdamW state (README.md:23's
    6.2 GB for 3B 2-way); ``state_dtype`` overrides for fp32 state."""

    def zeros(p):
        dt = state_dtype or p.dtype
        return jnp.zeros(p.shape, dt, device=p.sharding)

    count = jnp.zeros((), jnp.int32)
    leaf = jax.tree.leaves(params_sharded)[0]
    if isinstance(getattr(leaf, "sharding", None), NamedSharding):
        # Commit the step counter replicated on the params' mesh so the
        # whole state tree lives on ONE device set — required for e.g.
        # checkpoint restore, which places arrays exactly as templated.
        count = jax.device_put(count, NamedSharding(leaf.sharding.mesh,
                                                    P()))
    return optim.AdamState(mu=jax.tree.map(zeros, params_sharded),
                           nu=jax.tree.map(zeros, params_sharded),
                           count=count)


# ---------------------------------------------------------------- explicit

OVERLAP_MODES = ("none", "ring", "ring_fused", "ring_fused_pallas")


def _gather_leaf(x, spec: P, axis: str, quantized: bool = False,
                 overlap: str = "none", fuse_matmul=False,
                 quantized_grads: bool = False):
    """all_gather a shard back to full size along its sharded dim (no-op for
    leaves this axis doesn't shard).  ``quantized``: ship int8 + scales
    over the wire and dequantize after (the torchao fp8-all-gather twin,
    reference ``fp8/fp8_benchmark.py:79-81``).  Like torchao — which only
    low-precision-casts Linear weights — 1-D leaves (RMSNorm scales) stay
    in full precision: quantizing them saves negligible bandwidth and costs
    outsized numerics.  ``quantized_grads`` additionally quantizes those
    gathers' BACKWARD reduce-scatter (the EQuARX grad-traffic leg —
    ``quant.quantized_reduce_scatter``).

    ``overlap="ring"``: the gather runs as the ppermute ring
    (``C.ring_all_gather``) — bitwise-identical values and grads, but
    n-1 schedulable hops instead of one monolithic collective.
    ``fuse_matmul`` (ring_fused modes, layer-hook leaves only; False or
    the chunk-matmul impl name): a 2-D projection weight sharded along
    its contraction dim is NOT gathered — it returns as a
    :class:`C.RingShard` and the model's projection matmul runs it as
    the decomposed ``all_gather_matmul`` ("xla") or its Pallas
    tile-kernel twin ("pallas")."""
    for dim, name in enumerate(spec):
        if name == axis:
            if quantized and x.ndim > 1:
                from ..ops.quant import quantized_all_gather
                return quantized_all_gather(x, axis, dim, quantized_grads)
            if fuse_matmul and x.ndim == 2 and dim == 0:
                return C.RingShard(
                    x, axis, "pallas" if fuse_matmul == "pallas" else "xla")
            if overlap in ("ring", "ring_fused", "ring_fused_pallas"):
                return C.ring_all_gather(x, axis, dim)
            return C.all_gather(x, axis, axis=dim)
    return x


def microbatch_value_and_grad(loss_fn, params, batch, accum_steps: int):
    """Gradient accumulation over ``accum_steps`` microbatches:
    ``lax.scan`` over the leading-dim split of ``batch``, value_and_grad
    per microbatch, grads summed into a donated scan carry, one final
    /accum_steps — the per-microbatch collectives (FSDP gathers, TP
    rejoins, their transposes) then pipeline against the next
    microbatch's compute instead of arriving as one end-of-step burst.
    Remat-aware: each microbatch's forward re-runs under the model's own
    ``jax.checkpoint`` policy inside ``loss_fn``, so only one
    microbatch's activations (at the configured remat granularity) are
    ever live.  Returns ``(mean_loss, mean_grads)`` — identical to one
    full-batch step up to fp re-association of the batch reduction
    (pinned tight by tests/test_overlap.py)."""
    if accum_steps == 1:
        return jax.value_and_grad(loss_fn)(params, batch)
    B = jax.tree.leaves(batch)[0].shape[0]
    if B % accum_steps:
        raise ValueError(
            f"accum_steps={accum_steps} must divide the per-device "
            f"batch {B} (global batch / dp axis size)")
    micro = jax.tree.map(
        lambda t: t.reshape(accum_steps, B // accum_steps, *t.shape[1:]),
        batch)

    def body(carry, mbatch):
        g_acc, l_acc = carry
        loss, grads = jax.value_and_grad(loss_fn)(params, mbatch)
        return (jax.tree.map(jnp.add, g_acc, grads),
                l_acc + loss.astype(jnp.float32)), None

    init = (jax.tree.map(jnp.zeros_like, params),
            jnp.zeros((), jnp.float32))
    (g_sum, l_sum), _ = jax.lax.scan(body, init, micro)
    return (l_sum / accum_steps,
            jax.tree.map(lambda g: g / accum_steps, g_sum))


def make_fsdp_train_step(
    params_sharded,
    cfg: T.TransformerConfig,
    mesh: Mesh,
    axis: str = "dp",
    *,
    reshard_after_forward: bool = True,
    quantized_gather: bool = False,
    quantized_grads: bool = False,
    overlap: str = "none",
    accum_steps: int = 1,
    offload: str = "none",
    sp_axis: str | None = None,
    lr: float = 3e-4,
    lr_schedule: Callable | None = None,
    b1: float = 0.9,
    b2: float = 0.95,
    eps: float = 1e-8,
    donate: bool = True,
    loss_fn: Callable | None = None,
    state_precision: str = "full",
):
    """Jitted explicit-FSDP step:
    ``(param_shards, opt_state, batch) -> (param_shards, opt_state, loss)``.

    ``params_sharded`` provides the tree structure/specs to jit against;
    ``batch`` = (input_ids, labels) sharded on the batch dim (dp).
    ``loss_fn(params, batch, cfg, layer_hook=...)`` defaults to the
    causal-LM loss (models.transformer.lm_loss).

    ``sp_axis`` adds sequence/context parallelism (parallel/sequence.py):
    the batch's sequence dim shards over that mesh axis, attention runs
    as the ring (``ops/ring_attention.py``), and the sp-replicated param
    grads get an explicit mean-psum across the ring.

    ``lr_schedule``: optional ``count -> lr`` (e.g.
    ``optim.warmup_cosine_schedule``) evaluated on the optimizer step
    counter inside the jitted step; overrides the constant ``lr``.

    ``state_precision``: "full" (moments in the params' dtype,
    ``init_fsdp_opt_state``) or "int8" (``init_fsdp_opt_state8`` /
    ``optim8.adam8_update`` — int8-at-rest moments, ~half the largest
    resident block; pass the matching opt state).

    ``overlap`` (the overlap engine, SimpleFSDP arXiv:2411.00284):
    "none" = monolithic per-leaf all_gathers; "ring" = the same gathers
    decomposed into ppermute ring hops (bitwise-identical losses/grads —
    the backward is pinned to the monolithic psum_scatter transpose);
    "ring_fused" = 2-D projection weights stay sharded and their matmuls
    run as decomposed ``all_gather_matmul`` collective matmuls
    (numerically equivalent, not bitwise: the chunked contraction
    re-associates the K-sum); "ring_fused_pallas" = the same choreography
    with each per-chunk tile matmul lowered through the Pallas kernel
    (``ops.collectives.all_gather_matmul_pallas`` — bitwise-identical to
    ring_fused at whole-chunk blocks).  Both fused modes require the
    per-layer gather seam (reshard_after_forward=True), a dense model,
    and full-precision gathers.

    ``quantized_grads`` (requires ``quantized_gather``): the quantized
    gathers' backward reduce-scatter also runs two-shot int8 on the wire
    (``ops.quant.quantized_reduce_scatter`` — the EQuARX grad-traffic
    leg; ~4x fewer backward bus bytes, per-contribution half-quantum
    error bound).

    ``accum_steps``: microbatched gradient accumulation —
    ``lax.scan`` over accum_steps splits of the batch with a donated
    grad carry (see :func:`microbatch_value_and_grad`); must divide the
    per-device batch.

    ``offload`` (memory planner, ``memory_plan/offload.py``): "opt" /
    "opt_act" park the optimizer state in pinned host memory between
    steps — the jitted step streams it on-device (MoveToDevice) for the
    Adam update and back (MoveToHost) after, transfers XLA's scheduler
    can hide behind the backward.  Pass an opt state placed with
    ``memory_plan.offload_tree``; the step's state output returns to
    host placement.  "opt_act" additionally expects
    ``cfg.offload_activations`` (named remat saves offloaded).  On
    backends without a pinned_host space the step is built transfer-free
    and is bitwise-identical to ``offload="none"``.
    """
    T.require_dense_block(cfg, "parallel.fsdp.make_fsdp_train_step")
    ws = int(mesh.shape[axis])
    if overlap not in OVERLAP_MODES:
        raise ValueError(f"overlap={overlap!r}; choose from "
                         f"{OVERLAP_MODES}")
    if overlap.startswith("ring_fused"):
        if quantized_gather:
            raise ValueError(f"overlap={overlap!r} fuses full-precision "
                             "collective matmuls; it does not compose "
                             "with quantized_gather (use overlap='ring')")
        if not reshard_after_forward:
            raise ValueError(f"overlap={overlap!r} needs the per-layer "
                             "gather seam — reshard_after_forward=False "
                             "keeps gathered weights live, which "
                             "contradicts fused re-ringing")
        if getattr(cfg, "n_experts", 0):
            raise ValueError(f"overlap={overlap!r} covers dense "
                             "projection leaves only; MoE expert leaves "
                             "shard their expert dim, not a contraction "
                             "dim (use overlap='ring')")
    if quantized_grads and not quantized_gather:
        raise ValueError("quantized_grads quantizes the backward "
                         "reduce-scatter of the quantized gathers; it "
                         "requires quantized_gather=True")
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    from ..memory_plan.offload import (
        DEVICE_KIND, HOST_KIND, OFFLOAD_MODES as _OFF,
        stream_tree, supports_host_offload)
    if offload not in _OFF:
        raise ValueError(f"offload={offload!r}; choose from {_OFF}")
    if sp_axis is not None:
        cfg = dataclasses.replace(cfg, attention_impl="ring",
                                  sp_axis=sp_axis)
    base_loss = loss_fn or T.lm_loss
    # per-leaf LR multipliers: the MoE router trains slower when
    # cfg.moe_router_lr_mult < 1 (router-collapse mitigation, ST-MoE)
    lr_mults = None
    if getattr(cfg, "moe_router_lr_mult", 1.0) != 1.0:
        lr_mults = jax.tree_util.tree_map_with_path(
            lambda path, _leaf: (cfg.moe_router_lr_mult
                                 if any(getattr(k, "key", None) == "w_router"
                                        for k in path) else 1.0),
            params_sharded)
    specs = fsdp_specs(params_sharded, axis)
    check_divisibility(params_sharded, specs, mesh)
    layer_specs = specs["layers"]
    # Inside the scan body each stacked leaf has lost its layer dim, so its
    # sharded dim shifts from 1 to 0.
    hook_specs = jax.tree.map(lambda s: P(*s[1:]), layer_specs,  # spec-ok
                              is_leaf=lambda x: isinstance(x, P))

    fuse = {"ring_fused": "xla", "ring_fused_pallas": "pallas"}.get(
        overlap, False)

    def layer_hook(layer):
        with scope("fsdp_layer_gather"):
            return _spec_map(
                lambda x, s: _gather_leaf(x, s, axis, quantized_gather,
                                          overlap, fuse_matmul=fuse,
                                          quantized_grads=quantized_grads),
                layer, hook_specs)

    def step(shards, opt_state, batch):
        def sharded_loss(shards, batch):
            # Root group: embed / final_norm / lm_head gathered up front
            # (the root fully_shard wrap, reference train_fsdp.py:94).
            # Never matmul-fused: embed is a lookup table, not a
            # projection operand.
            with scope("fsdp_root_gather"):
                outer = {k: _gather_leaf(v, specs[k], axis,
                                         quantized_gather, overlap,
                                         quantized_grads=quantized_grads)
                         for k, v in shards.items() if k != "layers"}
            if reshard_after_forward:
                params = {**outer, "layers": shards["layers"]}
                return base_loss(params, batch, cfg, layer_hook=layer_hook)
            # ZeRO-2 mode: gather ALL layers once, keep them live through
            # the backward — more memory, half the gathers (the 3000 vs
            # 1849 tok/s knob, train_fsdp.py:85-86).
            with scope("fsdp_pre_gather_layers"):
                full_layers = _spec_map(
                    lambda x, s: _gather_leaf(
                        x, s, axis, quantized_gather, overlap,
                        quantized_grads=quantized_grads),
                    shards["layers"], layer_specs)
            params = {**outer, "layers": full_layers}
            return base_loss(params, batch, cfg, layer_hook=None)

        with scope("forward_backward"):
            # Grads w.r.t. the SHARDS: each all_gather transposes to a
            # psum_scatter — the FSDP backward reduce-scatter.  With
            # accum_steps > 1 the scan's per-microbatch transposes
            # pipeline against the next microbatch's forward.
            loss, grad_shards = microbatch_value_and_grad(
                sharded_loss, shards, batch, accum_steps)
        with scope("loss_mean"):
            loss = C.all_reduce(loss, axis, mean=True)
            if sp_axis is not None:
                loss = C.all_reduce(loss, sp_axis, mean=True)
        with scope("grad_mean"):
            # dp contributions were already summed into the shards by the
            # gathers' AD transposes; finish the mean.  Under SP the
            # params are replicated across sp_axis, so those grads need
            # an explicit mean-psum across the ring too.
            grad_shards = jax.tree.map(
                (lambda g: C.all_reduce(g, sp_axis, mean=True) / ws)
                if sp_axis is not None else (lambda g: g / ws),
                grad_shards)
        with scope("opt_step"):
            lr_t = lr_schedule(opt_state.count) if lr_schedule else lr
            if state_precision == "int8":
                from . import optim8
                shards, opt_state = optim8.adam8_update(
                    grad_shards, opt_state, shards,
                    lr=lr_t, b1=b1, b2=b2, eps=eps, lr_mults=lr_mults)
            else:
                shards, opt_state = optim.adam_update(
                    grad_shards, opt_state, shards,
                    lr=lr_t, b1=b1, b2=b2, eps=eps, lr_mults=lr_mults)
        return shards, opt_state, loss

    if state_precision == "int8":
        sspec = q8_state_specs(params_sharded, specs)
        state_specs = optim.AdamState(mu=sspec, nu=sspec, count=P())
    else:
        state_specs = optim.AdamState(mu=specs, nu=specs, count=P())
    batch_spec = P(axis) if sp_axis is None else P(axis, sp_axis)  # spec-ok
    sharded = C.smap(step, mesh,
                     in_specs=(specs, state_specs, batch_spec),
                     out_specs=(specs, state_specs, P()))
    if offload != "none" and supports_host_offload():
        # host-resident opt state: stream it on-device for the update and
        # back after — the MoveToDevice/MoveToHost pair the offload
        # contract declares (memory_plan.OffloadPlan).  Transfers sit
        # OUTSIDE shard_map (each leaf keeps its partition spec, only the
        # memory space changes) so the choreography inside is untouched.
        def offload_step(shards, opt_state, batch):
            opt_dev = stream_tree(opt_state, DEVICE_KIND)
            shards, opt_dev, loss = sharded(shards, opt_dev, batch)
            return shards, stream_tree(opt_dev, HOST_KIND), loss

        return jax.jit(offload_step,
                       donate_argnums=(0, 1) if donate else ())
    return jax.jit(sharded, donate_argnums=(0, 1) if donate else ())


# -------------------------------------------------------------------- auto

def make_fsdp_auto_train_step(
    params_sharded,
    cfg: T.TransformerConfig,
    mesh: Mesh,
    axis: str = "dp",
    *,
    lr: float = 3e-4,
    b1: float = 0.9,
    b2: float = 0.95,
    eps: float = 1e-8,
    donate: bool = True,
):
    """Library-mode FSDP: jit + NamedSharding constraints, XLA inserts and
    schedules the collectives (its scheduler may prefetch gathers — this is
    the variant that can beat the explicit one, as torch FSDP2 is to the
    reference's hand-rolled zero3)."""
    T.require_dense_block(cfg, "parallel.fsdp.make_fsdp_auto_train_step")
    specs = fsdp_specs(params_sharded, axis)
    check_divisibility(params_sharded, specs, mesh)
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                          is_leaf=lambda x: isinstance(x, P))
    sshard = optim.AdamState(mu=pshard, nu=pshard,
                             count=NamedSharding(mesh, P()))
    bshard = NamedSharding(mesh, P(axis))  # spec-ok

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: T.lm_loss(p, batch, cfg))(params)
        params, opt_state = optim.adam_update(
            grads, opt_state, params, lr=lr, b1=b1, b2=b2, eps=eps)
        return params, opt_state, loss

    return jax.jit(
        step,
        in_shardings=(pshard, sshard, (bshard, bshard)),
        out_shardings=(pshard, sshard, NamedSharding(mesh, P())),
        donate_argnums=(0, 1) if donate else ())
