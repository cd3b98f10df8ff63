"""Sequence/context parallelism: FSDP × ring-attention training.

The capability the reference lacks outright (SURVEY.md §5.7: long context
is handled there only by seq-len sweeps to 8192) and the one that defines
the TPU build's scaling story past a single chip's HBM: shard the
*sequence* dimension of activations across a mesh axis and run exact
causal attention with K/V blocks circulating the ring
(``ops/ring_attention.py``).

Layout over a 2-D mesh ``("dp", "sp")``:

  * batch dim sharded on ``dp``; sequence dim sharded on ``sp``
  * params FSDP-sharded over ``dp`` (per-layer gather inside the remat
    scan — the explicit choreography of ``parallel/fsdp.py``) and
    replicated over ``sp``
  * forward: everything except attention is token-local (matmuls, norms,
    the streamed loss); attention is the ring
  * backward: the dp all_gathers transpose to psum_scatters (FSDP's
    reduce-scatter), the ring's ppermutes transpose to reverse-direction
    ppermutes, and the sp-replicated param grads need one explicit
    psum over ``sp``

RoPE positions and causal structure use each rank's global chunk offset
(``models/transformer.py:hidden_states`` applies ``axis_index(sp) · S``
when ``cfg.sp_axis`` is set).  The loss is a mean over local tokens;
chunks are equal-sized, so the all-axis mean of means equals the global
mean.

The actual step builder lives in ``fsdp.make_fsdp_train_step`` (one
choreography, optional ``sp_axis``) so the FSDP gather logic and its
knobs (reshard_after_forward, quantized_gather, loss_fn) exist once and
apply to the SP variant too; this module is the SP-facing surface.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from ..models import transformer as T
from .fsdp import make_fsdp_train_step


def sp_config(cfg: T.TransformerConfig, sp_axis: str = "sp",
              layout: str = "contiguous") -> T.TransformerConfig:
    """The config switched to ring attention over ``sp_axis``.
    ``layout="zigzag"`` selects the balanced striped layout (~half the
    ring's score FLOPs; see ``ops/ring_attention.py``) — feed batches
    through ``zigzag_shuffle`` then."""
    return dataclasses.replace(cfg, attention_impl="ring", sp_axis=sp_axis,
                               ring_layout=layout)


def _zigzag_perm(n_dev: int) -> np.ndarray:
    """Stripe order giving device r stripes (r, 2D−1−r) under contiguous
    equal sharding: [0, 2D−1, 1, 2D−2, ...]."""
    return np.array([s for r in range(n_dev)
                     for s in (r, 2 * n_dev - 1 - r)])


def zigzag_shuffle(x, n_dev: int, axis: int = 1):
    """Reorder a GLOBAL sequence dim into zigzag stripe order, so a plain
    contiguous P(sp) sharding lands stripes (r, 2D−1−r) on device r.
    Apply to input_ids and labels identically — token-mean losses are
    permutation-invariant, so training semantics are unchanged."""
    S = x.shape[axis]
    if S % (2 * n_dev):
        raise ValueError(f"sequence length {S} must divide into "
                         f"2·{n_dev} zigzag stripes")
    w = S // (2 * n_dev)
    shape = x.shape
    stripes = x.reshape(*shape[:axis], 2 * n_dev, w, *shape[axis + 1:])
    out = jnp.take(stripes, _zigzag_perm(n_dev), axis=axis)
    return out.reshape(shape)


def zigzag_unshuffle(x, n_dev: int, axis: int = 1):
    """Inverse of ``zigzag_shuffle`` (restore natural sequence order)."""
    S = x.shape[axis]
    if S % (2 * n_dev):
        raise ValueError(f"sequence length {S} must divide into "
                         f"2·{n_dev} zigzag stripes")
    w = S // (2 * n_dev)
    shape = x.shape
    stripes = x.reshape(*shape[:axis], 2 * n_dev, w, *shape[axis + 1:])
    out = jnp.take(stripes, np.argsort(_zigzag_perm(n_dev)), axis=axis)
    return out.reshape(shape)


def make_sp_train_step(
    params_sharded,
    cfg: T.TransformerConfig,
    mesh: Mesh,
    *,
    dp_axis: str = "dp",
    sp_axis: str = "sp",
    **kwargs,
):
    """Jitted FSDP×SP step:
    ``(param_shards, opt_state, batch) -> (param_shards, opt_state, loss)``
    with ``batch`` = (input_ids, labels), both (B, S_global), sharded
    P(dp, sp).  ``params_sharded`` is the dp-FSDP-sharded tree
    (``fsdp.shard_params_fsdp`` — sp sees replicas).  Accepts every
    ``make_fsdp_train_step`` knob (reshard_after_forward, lr, donate, …).
    """
    T.require_dense_block(cfg, "parallel.sequence.make_sp_train_step")
    return make_fsdp_train_step(params_sharded, cfg, mesh, axis=dp_axis,
                                sp_axis=sp_axis, **kwargs)
