"""Sliding-window and full GQA attention mixed in one stack, output-gated,
over dense layers and then held and shared experts (the layer
Trinity-Large-Preview publishes, ``model_type`` ``afmoe``), for the serving
engine's paged layer body and for the cache-less ``transformer.forward``.

It holds what DIFFERS from the blocks that are there and copies none of
them: the expert layer (routing over the router's whole width, the held
experts' part of the routed sum, the shared expert, the counters), the
leading dense layers and the sandwich residual of the MLP are
``models/mla_moe.py``'s as they stand (``mla_moe.mlp``), with a selection
bias the router's leaf ``router_bias`` brings; the heads' output gate is
``models/gdn_moe.py``'s (``gate_heads``).  Written here: which layer
attends through a window, the attention's projections with per-head
QK-norm and a rotary embedding on the window layers alone, the scaled
embedding, and the composition.

``x`` is the residual stream ``(B, S, H)``; every norm is a plain RMSNorm
of ``rms_norm_eps`` (weight initialised 1); no bias but the router's.
Layer ``i`` (0-based) is a FULL-attention layer where ``(i + 1) %
global_attn_every_n_layers == 0`` and a WINDOW layer otherwise, whose row
``t`` sees the keys ``s`` with ``t - sliding_window < s <= t``::

    x_0 = embed[ids] * sqrt(H)                                  (mup_enabled)
    r = norm(x; ln1);   q, k, v = r wq, r wk, r wv;   gate = r wg
    q_j = norm(q_j; q_norm),  k_m = norm(k_m; k_norm)     over ONE head's hd
    window layer: q, k = rope(q), rope(k)   split-half over the whole head,
                  theta ``rope_theta``, no scaling; a full layer: no rotary
    a_j = softmax(q_j k_{m(j)}^T / sqrt(hd) + mask) v_{m(j)},  m(j) = j // (n / n_kv)
    x <- x + norm([a_j * sigmoid(gate_j)]_j wo; post_attn_norm)      sandwich
    x <- x + norm(MLP(norm(x; ln2)); post_mlp_norm)                  sandwich
    logits = norm(x_L; final_norm) lm_head                           untied

MLP: layers ``0 .. num_dense_layers - 1`` SwiGLU of ``intermediate_size``;
the rest ``mla_moe.expert_mlp``: ``s = sigmoid(r2 w_router)`` over
``router_width`` experts in float32, CHOSEN = top-``num_experts_per_tok``
of ``s + router_bias`` (the bias chooses and does not weigh), ``w_e =
routed_scaling_factor . s_e / (sum_chosen s + 1e-20)``, this program HOLDS
``num_experts`` routed experts (ids ``expert_offset`` onwards) of
``moe_intermediate_size`` and one unweighted shared SwiGLU of
``num_shared_experts * moe_intermediate_size``.

What one token caches in a layer is its K and V rows ``(n_kv, hd)``, the
window layers' rotated.  A full layer keeps them for the whole context; a
window layer needs the last ``sliding_window`` of them, which the serving
pool holds in a ring of its own page class (``serving/kv_pool.py``).

Parameter tree: ``embed`` (V, H), ``lm_head`` (H, V), ``final_norm`` (H,)
and ``layers``, a tuple of one dict a layer (nothing stacked): ``ln1``,
``wq``, ``wg`` (H, n hd), ``wk``, ``wv`` (H, n_kv hd), ``q_norm``,
``k_norm`` (hd,), ``wo`` (n hd, H), ``post_attn_norm``, ``ln2``,
``post_mlp_norm``; a dense layer adds ``w_gate``/``w_up``/``w_down``; an
expert layer ``w_router`` (H, router width), ``router_bias`` (router
width,) float32, the held experts' ``we_gate``/``we_up`` (E, H, F) and
``we_down`` (E, F, H), the shared expert's ``ws_gate``/``ws_up``/
``ws_down``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..utils.profiling import scope
from . import mla_moe as M
from .gdn_hybrid import attention_scale  # noqa: F401  (None: 1/sqrt(hd))
from .gdn_moe import gate_heads
from .mla_moe import (COUNTS_FROM_ZERO, final_norm,  # noqa: F401
                      mixer_input, mlp)

#: what the engine counts for this block in ``stats``.  The first six are
#: summed on the device through a burst (``engine.device_counters``): the
#: expert layers' four, then per decode step and live slot the cached rows
#: a window layer reads, ``min(len, sliding_window)``, and a full layer,
#: ``len``.  The host counts the rest: (row, visible key) pairs of the
#: prefill chunks' valid rows in ONE window layer, and the most pages of
#: each class that were granted at once
COUNTERS = M.COUNTERS + ("window_rows_read", "full_rows_read",
                         "window_pairs_prefilled", "window_pages_peak",
                         "full_pages_peak")
DEVICE_COUNTERS = COUNTERS[:6]

#: how this block's router scores an expert (``mla_moe.route``)
ROUTER_SCORING = "sigmoid"

#: the scopes the engine opens beneath ``attn_core`` round this block's
#: paged attention: the full layers' as the hybrid with expert layers
#: names it, the window layers' apart (``profiling.WINDOW_SUBSCOPES``)
PAGED_ATTENTION_SCOPE = "attn_paged"
WINDOW_ATTENTION_SCOPE = "attn_window"

#: the window layers' rotary tables are the serving engine's own, over the
#: whole head at ``rope_theta`` as the dense block's (None: the caller's),
#: and a full layer is handed none
rope_tables = None
NOPE_KINDS = ("full",)


def refuse(cfg, what: str):
    raise NotImplementedError(
        f"the sliding-window + full attention block with held experts "
        f"(sliding_window={cfg.sliding_window}, full attention every "
        f"{cfg.global_attn_every_n_layers} layers, num_experts="
        f"{cfg.num_experts} of {cfg.router_width} held) is served by "
        f"serving/engine.py and run cache-less by models/transformer."
        f"forward only; {what} is not built for it (ROADMAP: mechanisms "
        f"the system cannot run yet)")


def check_config(cfg) -> None:
    """Called from ``TransformerConfig.__post_init__`` when the block is
    selected: the block is what the module docstring writes down, and a
    field that asks for another variant is refused by name."""
    need = ("global_attn_every_n_layers", "moe_intermediate_size",
            "router_width", "num_experts", "num_shared_experts",
            "num_experts_per_tok")
    missing = [k for k in need if getattr(cfg, k) <= 0]
    if missing:
        raise ValueError(f"sliding_window={cfg.sliding_window} selects the "
                         f"window + full attention block, which also needs "
                         f"{missing} > 0")
    if not 0 <= cfg.num_dense_layers <= cfg.num_hidden_layers:
        raise ValueError("num_dense_layers must lie in "
                         "[0, num_hidden_layers]")
    M.check_held_experts(cfg)
    if cfg.resolved_head_dim % 2:
        raise ValueError("head_dim must be even (rotary pairs)")
    for key, want in (("sandwich_norm", True), ("norm_topk_prob", True),
                      ("nope_interval", 0), ("tie_word_embeddings", False),
                      ("n_experts", 0), ("n_routed_experts", 0),
                      ("n_shared_experts", 0), ("first_k_dense_replace", 0),
                      ("kv_lora_rank", 0), ("linear_key_head_dim", 0),
                      ("attention_impl", "xla")):
        if getattr(cfg, key) != want:
            raise ValueError(f"the window + full attention block is built "
                             f"with {key}={want!r} only, got "
                             f"{getattr(cfg, key)!r}")


def layer_kinds(cfg) -> tuple[str, ...]:
    """One entry a layer: ``"full"`` (K/V rows in whole-context pages)
    where ``(i + 1) % global_attn_every_n_layers == 0``, else ``"window"``
    (K/V rows in a ring of the window page class)."""
    return tuple("window" if (li + 1) % cfg.global_attn_every_n_layers
                 else "full" for li in range(cfg.num_hidden_layers))


def is_expert_layer(li: int, cfg) -> bool:
    return li >= cfg.num_dense_layers


def param_count(cfg) -> int:
    h, hd = cfg.hidden_size, cfg.resolved_head_dim
    nq, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    F = cfg.moe_intermediate_size
    attn = h * hd * (3 * nq + 2 * nkv) + 2 * hd + 4 * h
    dense = attn + 3 * h * cfg.intermediate_size
    expert = attn + h * cfg.router_width + cfg.router_width \
        + 3 * h * F * (cfg.num_experts + cfg.num_shared_experts)
    n_dense = cfg.num_dense_layers
    return n_dense * dense + (cfg.num_hidden_layers - n_dense) * expert \
        + 2 * cfg.vocab_size * h + h


# ------------------------------------------------------------------- init

def init_params(key: jax.Array, cfg) -> dict:
    """``transformer.init_params`` for this block: truncated normal 0.02,
    the projections back into the residual stream scaled by
    1/sqrt(2 . layers), norms at one, the router's selection bias
    truncated normal 0.1 in float32 (of the order of the spread of the
    sigmoid scores it is added to, so that it changes which experts are
    chosen)."""
    h, hd = cfg.hidden_size, cfg.resolved_head_dim
    nq, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    F, Fe = cfg.intermediate_size, cfg.moe_intermediate_size
    E, Fs = cfg.num_experts, cfg.num_shared_experts * Fe
    out_std = 0.02 / math.sqrt(2 * cfg.num_hidden_layers)
    keys = iter(jax.random.split(key, 2 + 13 * cfg.num_hidden_layers))

    def tn(shape, std=0.02, dtype=cfg.dtype):
        return (std * jax.random.truncated_normal(
            next(keys), -2, 2, shape, jnp.float32)).astype(dtype)

    ones = lambda *shape: jnp.ones(shape, cfg.dtype)  # noqa: E731

    def layer(li):
        out = {"ln1": ones(h), "wq": tn((h, nq * hd)), "wg": tn((h, nq * hd)),
               "wk": tn((h, nkv * hd)), "wv": tn((h, nkv * hd)),
               "q_norm": ones(hd), "k_norm": ones(hd),
               "wo": tn((nq * hd, h), out_std),
               "post_attn_norm": ones(h), "ln2": ones(h),
               "post_mlp_norm": ones(h)}
        if not is_expert_layer(li, cfg):
            return {**out, "w_gate": tn((h, F)), "w_up": tn((h, F)),
                    "w_down": tn((F, h), out_std)}
        return {**out, "w_router": tn((h, cfg.router_width)),
                "router_bias": tn((cfg.router_width,), 0.1, jnp.float32),
                "we_gate": tn((E, h, Fe)), "we_up": tn((E, h, Fe)),
                "we_down": tn((E, Fe, h), out_std),
                "ws_gate": tn((h, Fs)), "ws_up": tn((h, Fs)),
                "ws_down": tn((Fs, h), out_std)}

    return {
        "embed": tn((cfg.vocab_size, h)),
        "layers": tuple(layer(li) for li in range(cfg.num_hidden_layers)),
        "final_norm": ones(h),
        "lm_head": tn((h, cfg.vocab_size)),
    }


# ------------------------------------------------- what the block brings

def embed(params, ids, cfg):
    """``embed[ids]``, times ``sqrt(H)`` under ``mup_enabled``."""
    x = params["embed"].astype(cfg.dtype)[ids]
    if cfg.mup_enabled:
        x = (x.astype(jnp.float32) * math.sqrt(cfg.hidden_size)).astype(
            cfg.dtype)
    return x


def attention_qkv(x, layer, *, cfg, rope):
    """``q`` (B, S, n, hd), ``k``, ``v`` (B, S, n_kv, hd) and the heads'
    output ``gate`` (B, S, n, hd) from the residual stream: the
    pre-attention norm, the four projections, the per-head norms of q and
    k, and the rotary embedding with the tables ``rope`` = (cos, sin)
    ((B,) S, hd / 2), or none where ``rope`` is None (a full layer)."""
    from .transformer import _dense, rms_norm
    B, S, _ = x.shape
    hd, eps = cfg.resolved_head_dim, cfg.rms_norm_eps
    nq, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    dense = _dense(cfg)
    r = rms_norm(x, layer["ln1"], eps)
    q = rms_norm(dense(r, layer["wq"]).reshape(B, S, nq, hd),
                 layer["q_norm"], eps)
    k = rms_norm(dense(r, layer["wk"]).reshape(B, S, nkv, hd),
                 layer["k_norm"], eps)
    v = dense(r, layer["wv"]).reshape(B, S, nkv, hd)
    gate = dense(r, layer["wg"]).reshape(B, S, nq, hd)
    if rope is not None:
        q, k = M._rope(q, *rope), M._rope(k, *rope)
    return q, k, v, gate


def attention_output(attn, gate, x, layer, *, cfg):
    """The heads' outputs ``attn`` (B, S, ..heads.., hd) float32, each
    gated by ``sigmoid(gate)``, through ``wo`` and the post-attention
    sandwich norm onto the residual stream."""
    return M.attention_output(gate_heads(attn, gate), None, x, layer,
                              cfg=cfg)


# ------------------------------------------------- the cache-less forward

def hidden_states(params, input_ids, cfg):
    """(B, S) ids -> final-norm hidden states (B, S, H): the whole
    sequence at once, materialised attention under a causal mask, banded
    in the window layers; no cache, no ring."""
    from .transformer import _rope_tables
    S = input_ids.shape[1]
    hd = cfg.resolved_head_dim
    rep = cfg.num_attention_heads // cfg.num_key_value_heads
    with scope("embed"):
        x = embed(params, input_ids, cfg)
        rope = _rope_tables(S, hd, cfg.rope_theta)
    t = jnp.arange(S)
    causal = t[None, :] <= t[:, None]
    band = jnp.logical_and(causal, t[None, :] > t[:, None] - cfg.sliding_window)

    for kind, layer in zip(layer_kinds(cfg), params["layers"]):
        window = kind == "window"
        with scope("attn_qkv"):
            q, k, v, gate = attention_qkv(x, layer, cfg=cfg,
                                          rope=rope if window else None)
        with scope("attn_core"):
            B = q.shape[0]
            qg = q.reshape(B, S, -1, rep, hd)
            s = jnp.einsum("bsgrh,bkgh->bgrsk", qg, k,
                           preferred_element_type=jnp.float32) / math.sqrt(hd)
            p = jax.nn.softmax(jnp.where(band if window else causal, s,
                                         -1e30), axis=-1)
            o = jnp.einsum("bgrsk,bkgh->bsgrh", p.astype(v.dtype), v,
                           preferred_element_type=jnp.float32)
        with scope("attn_out"):
            x = attention_output(o, gate, x, layer, cfg=cfg)
        with scope("mlp"):
            x, _ = mlp(x, layer, cfg=cfg)
    with scope("loss_head"):
        return final_norm(x, params, cfg)
