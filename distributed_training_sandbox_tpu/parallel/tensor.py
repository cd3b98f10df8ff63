"""Tensor parallelism: Megatron-style sharded transformer layers.

The reference's course outline names TP ("Week 4: Tensor Parallelism from
scratch") but never implements it (SURVEY.md §2.2: ABSENT) — on TPU it is
a natural named-mesh-axis extension and the second axis of this build's
2-D/3-D scaling story (dp × tp, dp × sp).

Layout over the ``tp`` axis (the classic column-then-row pairing):

  * attention: wq/wk/wv shard their OUTPUT dim — each device owns
    ``num_heads / tp`` query heads (and the matching share of KV heads;
    GQA group structure is preserved because nq and nkv divide evenly);
    attention itself is embarrassingly parallel over heads; wo shards its
    INPUT dim, so each device's contribution is a partial sum → one
    ``psum`` rejoins the residual stream.
  * MLP: w_gate/w_up shard the intermediate dim (column), w_down shards
    its input dim (row) → one ``psum``.
  * norms, embedding, unembedding: replicated (their grads are mean-psum'd
    across ``tp`` at step time).

Two psums per layer per direction — the canonical Megatron choreography,
visible and countable in the HLO like every other strategy here.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import transformer as T
from ..ops import collectives as C
from ..utils.profiling import scope
from . import optim


def check_tp_divisibility(cfg: T.TransformerConfig, tp: int) -> None:
    dims = [("num_attention_heads", cfg.num_attention_heads),
            ("num_key_value_heads", cfg.num_key_value_heads)]
    if cfg.n_experts and cfg.moe_ffn:
        dims.append(("moe_ffn", cfg.moe_ffn))
    else:   # dense MLP, or experts defaulting to intermediate_size
        dims.append(("intermediate_size", cfg.intermediate_size))
    bad = [(n, v) for n, v in dims if v % tp]
    if bad:
        raise ValueError(f"tp={tp} must divide " + ", ".join(
            f"{n}={v}" for n, v in bad))


def tp_specs(params, axis: str = "tp") -> dict:
    """PartitionSpec tree for Megatron sharding.  Dense stacked layer
    leaves are (L, in, out): column-parallel ones shard dim 2,
    row-parallel ones (wo, w_down) shard dim 1.  MoE expert leaves are
    (L, E, in, out): the SAME column/row roles one dim later — each
    expert's FFN is Megatron-split across the tp group (w_router, like
    every other dense leaf, replicated).  A serving engine's fused
    per-layer ``wqkv`` leaves (``serving/engine.py:_dense_serving_tree``)
    are (in, out) with each shard's q, k and v columns side by side:
    column-parallel too."""
    row = {"wo", "w_down"}
    col = {"wq", "wk", "wv", "wqkv", "w_gate", "w_up"}

    def leaf_spec(path, leaf):
        name = next((getattr(k, "key", None) for k in reversed(path)
                     if getattr(k, "key", None)), None)
        if name in col:     # the output dim is the last one
            return P(*(None,) * (leaf.ndim - 1), axis)
        if name in row:
            return (P(None, None, axis, None) if leaf.ndim == 4
                    else P(None, axis, None))
        return P()

    return jax.tree_util.tree_map_with_path(leaf_spec, params)


def shard_params_tp(params, mesh: Mesh, axis: str = "tp"):
    specs = tp_specs(params, axis)
    return jax.tree.map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)),
        params, specs, is_leaf=lambda x: isinstance(x, P))


def tp_lm_loss(params, batch, cfg: T.TransformerConfig, *,
               axis: str = "tp", overlap: str = "none") -> jax.Array:
    """Causal-LM loss with Megatron TP layers (shard_map only): the
    shared decoder body (``transformer._layer_body``) runs with
    ``tp_axis`` set — local head/intermediate shards, two psums per layer
    — via the ``layer_body`` seam, so the scaffold AND the layer math
    exist exactly once.  ``params`` hold LOCAL shards; embedding/norms/
    loss are replicated and identical on every tp rank.

    Composes with sequence parallelism: with ``cfg.sp_axis`` set (ring
    attention), each device holds its tp-share of heads AND its sp-chunk
    of the sequence — the KV ring circulates over ``sp_axis`` within
    each tp group, carrying only the local heads.

    ``overlap="ring"`` decomposes the two per-layer row-parallel rejoin
    psums into psum_scatter + ring all-gather (bitwise-identical — see
    ``ops.collectives.decomposed_all_reduce``)."""
    import functools
    return T.lm_loss(params, batch, cfg, layer_body=functools.partial(
        T._layer_body, tp_axis=axis, tp_overlap=overlap))


def make_tp_train_step(
    params_sharded,
    cfg: T.TransformerConfig,
    mesh: Mesh,
    *,
    dp_axis: str = "dp",
    tp_axis: str = "tp",
    sp_axis: str | None = None,
    overlap: str = "none",
    accum_steps: int = 1,
    lr: float = 3e-4,
    b1: float = 0.9,
    b2: float = 0.95,
    eps: float = 1e-8,
    donate: bool = True,
    loss_fn: Callable | None = None,
):
    """Jitted dp×tp step:
    ``(param_shards, opt_state, batch) -> (param_shards, opt_state, loss)``.
    Batch (input_ids, labels) sharded P(dp); params tp-sharded per
    ``tp_specs`` and replicated over dp (grads mean-psum'd over every
    axis each leaf is replicated on).

    ``sp_axis`` makes it the full 3-D dp×sp×tp step: the batch's
    sequence dim shards over ``sp_axis`` and attention becomes the KV
    ring over it (carrying only this device's tp-share of heads).

    ``overlap="ring"``: the per-layer row-parallel rejoin psums run
    decomposed (psum_scatter + ring all-gather) — bitwise-identical
    loss/grads, tp-1 schedulable hops per rejoin.  ``overlap="q8"``:
    the rejoin psums run as EQuARX two-shot quantized all-reduces
    (``ops.quant.quantized_all_reduce`` — int8 codes + scales on the
    wire, ~4x fewer bus bytes, per-contribution half-quantum error
    bound; grad psums stay full-precision).  Both apply to the default
    ``tp_lm_loss`` only (a custom ``loss_fn`` owns its own
    collectives).  ``accum_steps``: microbatched gradient accumulation
    over leading-dim batch splits (``fsdp.microbatch_value_and_grad``)."""
    T.require_dense_block(cfg, "parallel.tensor.make_tp_train_step")
    ws_dp = int(mesh.shape[dp_axis])
    ws_tp = int(mesh.shape[tp_axis])
    check_tp_divisibility(cfg, ws_tp)
    if overlap not in ("none", "ring", "q8"):
        raise ValueError(f"overlap={overlap!r}; the tp step supports "
                         f"'none', 'ring' or 'q8'")
    if overlap != "none" and loss_fn is not None:
        raise ValueError(f"overlap={overlap!r} rewires tp_lm_loss's "
                         "rejoin psums; a custom loss_fn owns its own "
                         "collectives — rewire them there instead")
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    if sp_axis is None and cfg.sp_axis is not None:
        raise ValueError(
            f"cfg.sp_axis={cfg.sp_axis!r} (ring attention) but "
            f"make_tp_train_step got sp_axis=None — the batch would "
            f"replicate over {cfg.sp_axis!r} and sp grads would never "
            f"sync.  Pass sp_axis={cfg.sp_axis!r} (the step sets the "
            f"ring config itself).")
    n_total = ws_dp * ws_tp
    rep_axes = [dp_axis]
    if sp_axis is not None:
        cfg = dataclasses.replace(cfg, attention_impl="ring",
                                  sp_axis=sp_axis)
        n_total *= int(mesh.shape[sp_axis])
        rep_axes.append(sp_axis)
    # loss_fn contract: (params, batch, cfg) -> scalar, same as fsdp's;
    # a loss that declares an ``axis`` parameter (like tp_lm_loss) gets
    # the tp axis forwarded.
    if loss_fn is None:
        base_loss = lambda p, b, c: tp_lm_loss(p, b, c, axis=tp_axis,
                                               overlap=overlap)
    else:
        import inspect
        if "axis" in inspect.signature(loss_fn).parameters:
            base_loss = lambda p, b, c: loss_fn(p, b, c, axis=tp_axis)
        else:
            base_loss = loss_fn
    specs = tp_specs(params_sharded, tp_axis)

    def sync_grad(g, spec):
        # Sum the copies over every axis this leaf is replicated on (one
        # fused psum over the combined group), then normalize by total
        # device count: grads of the global-mean loss.
        axes = tuple(rep_axes) + ((tp_axis,) if tp_axis not in spec
                                  else ())
        return lax.psum(g, axes) / n_total

    def step(shards, opt_state, batch):
        with scope("forward_backward"):
            from .fsdp import microbatch_value_and_grad
            loss, grads = microbatch_value_and_grad(
                lambda p, b: base_loss(p, b, cfg), shards, batch,
                accum_steps)
        with scope("loss_mean"):
            # one fused mean over every axis (tp ranks hold identical
            # losses; including tp re-establishes replication for the
            # P() out_spec explicitly).
            loss = lax.pmean(loss, tuple(rep_axes + [tp_axis]))
        with scope("grad_sync"):
            grads = jax.tree.map(
                sync_grad, grads, specs,
                is_leaf=lambda x: isinstance(x, P))
        with scope("opt_step"):
            shards, opt_state = optim.adam_update(
                grads, opt_state, shards, lr=lr, b1=b1, b2=b2, eps=eps)
        return shards, opt_state, loss

    state_specs = optim.AdamState(mu=specs, nu=specs, count=P())
    batch_spec = P(dp_axis) if sp_axis is None else P(dp_axis, sp_axis)  # spec-ok
    sharded = C.smap(step, mesh,
                     in_specs=(specs, state_specs, batch_spec),
                     out_specs=(specs, state_specs, P()))
    return jax.jit(sharded, donate_argnums=(0, 1) if donate else ())
