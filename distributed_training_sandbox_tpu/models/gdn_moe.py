"""The gated delta-rule hybrid with an expert layer under every mixer (the
layer Qwen3-Next-80B-A3B publishes), for the serving engine's paged layer
body and for the cache-less ``transformer.forward``.

It COMPOSES what two modules hold and copies neither: the linear mixer
(conv, the recurrence's three forms, the state's layouts, the mixer's
leaves) is ``models/gdn_hybrid.py``'s, with two value heads a key head and
``beta = sigmoid`` (no factor 2); the expert layer (routing over the
router's whole width, the held experts' part of the routed sum, the shared
expert, the counters) is ``models/mla_moe.py``'s, with a softmax router and
a sigmoid-gated shared expert.  What is written here is what differs: the
pre-norm residual path with zero-centred norms, the output-gated attention
with per-head QK-norm and a partial rotary embedding, and the composition.
The layer loop itself is ``gdn_hybrid.hidden_states`` and the engine's
``_paged_block_forward``, which call this module through
``cfg.block_module`` (the engine loop's docstring: what a block brings).

``x`` is the residual stream ``(B, S, H)``; every norm is an RMSNorm of
``rms_norm_eps``; ZERO-CENTRED: ``norm(x; w) = x / rms(x) * (1 + w)``, ``w``
initialised 0; no bias anywhere.  Layer ``i`` (0-based) is a full-attention
layer where ``(i + 1) % full_attention_interval == 0``::

    h = x + Mixer(norm(x; input_norm))                       zero-centred
    y = h + MoE(norm(h; post_attn_norm))                     zero-centred
    logits = norm(x_L; final_norm) lm_head                   zero-centred, untied

Full-attention mixer (``n`` heads of ``hd``, ``n_kv`` KV heads, ``rot =
partial_rotary_factor * hd`` rotary dims)::

    [q_j | gate_j] = r wq  per head j     (wq: H x n x 2 hd, a head's q then its gate)
    q_j = rope(norm(q_j; q_norm)),  k_m = rope(norm(r wk |_m; k_norm)),  v_m = r wv |_m
                                     norms zero-centred, over ONE head's hd
    rope: split-half rotation of dims 0 .. rot - 1 of a head (theta
          ``rope_theta``, no scaling); dims rot .. hd - 1 pass through
    a_j = causal softmax(q_j k_{m(j)}^T / sqrt(hd)) v_{m(j)},   m(j) = j // (n / n_kv)
    Mixer(r) = [a_j * sigmoid(gate_j)]_j wo

What one token caches in such a layer is its K and V rows ``(n_kv, hd)``,
in pages, rotated.

Linear mixer: ``gdn_hybrid``'s (its docstring), read from ``r``::

    Mixer(r)_t = [norm(o_t; o_norm) * silu(r_t w_g)] w_o      o_norm plain (init 1)

MoE (router width ``router_width``, ``num_experts_per_tok`` chosen, this
program HOLDS ``num_experts`` routed experts, ids ``expert_offset``
onwards, each of ``moe_intermediate_size``; one shared expert of
``shared_expert_intermediate_size``)::

    p = softmax(r2 w_router) over the whole width, float32;  T = top-k(p)
    w_e = p_e / sum_{e' in T} p_e'
    MoE(r2) = sum_{e in T, e held} w_e SwiGLU_e(r2)
              + sigmoid(r2 ws_sigmoid) * SwiGLU_shared(r2)

``w_e`` is normalised over the CHOSEN experts, held here or not; what the
absent experts would add is left out (one rank's part under expert
parallelism; on one chip the layer runs without its exchange).

Parameter tree: ``embed`` (V, H), ``lm_head`` (H, V), ``final_norm`` (H,)
and ``layers``, a tuple of one dict a layer (two kinds, nothing stacked).
Every layer holds ``input_norm``, ``post_attn_norm`` (H,), ``w_router``
(H, router width), the held experts' ``we_gate``/``we_up`` (E, H, F) and
``we_down`` (E, F, H), the shared expert's ``ws_gate``/``ws_up`` (H, Fs),
``ws_down`` (Fs, H) and ``ws_sigmoid`` (H, 1); a full-attention layer adds
``wq`` (H, n 2 hd), ``wk``, ``wv`` (H, n_kv hd), ``wo`` (n hd, H),
``q_norm``, ``k_norm`` (hd,); a linear layer the leaves of
``gdn_hybrid.linear_mixer_params``.  A published multi-token-prediction
layer is not part of the block.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import gdn_hybrid as G
from . import mla_moe as M
from .gdn_hybrid import (COUNTS_FROM_ZERO, NOPE_KINDS,  # noqa: F401
                         attention_scale, embed, hidden_states, layer_kinds)

#: what the engine counts for this block in ``stats``: the expert layers'
#: four and the live states, summed on the device through a burst (the
#: first five); slots reset at a grant and rows scanned, on the host
COUNTERS = M.COUNTERS + G.COUNTERS
DEVICE_COUNTERS = COUNTERS[:5]

#: how this block's router scores an expert (``mla_moe.route``): a softmax
#: over the router's whole width
ROUTER_SCORING = "softmax"

#: the scope the engine opens round this block's paged attention, so that a
#: reader can tell it from the linear layers' step or scan under
#: ``attn_core`` (``profiling.ATTENTION_SUBSCOPES``)
PAGED_ATTENTION_SCOPE = "attn_paged"


def refuse(cfg, what: str):
    raise NotImplementedError(
        f"the gated delta-rule hybrid block with expert layers "
        f"(linear_key_head_dim={cfg.linear_key_head_dim}, num_experts="
        f"{cfg.num_experts} of {cfg.router_width} held, full attention "
        f"every {cfg.full_attention_interval} layers) is served by "
        f"serving/engine.py and run cache-less by models/transformer."
        f"forward only; {what} is not built for it (ROADMAP: mechanisms "
        f"the system cannot run yet)")


def rotary_dim(cfg) -> int:
    """Leading dims of a head that the rotary embedding rotates."""
    return int(cfg.resolved_head_dim * cfg.partial_rotary_factor)


def check_config(cfg) -> None:
    """Called from ``TransformerConfig.__post_init__`` when the block is
    selected: the block is what the module docstring writes down, and a
    field that asks for another variant is refused by name."""
    G.check_linear(cfg)
    need = ("moe_intermediate_size", "router_width", "num_experts_per_tok",
            "shared_expert_intermediate_size")
    missing = [k for k in need if getattr(cfg, k) <= 0]
    if missing:
        raise ValueError(f"num_experts={cfg.num_experts} beside "
                         f"linear_key_head_dim selects the hybrid with "
                         f"expert layers, which also needs {missing} > 0")
    M.check_held_experts(cfg)
    rot = cfg.resolved_head_dim * cfg.partial_rotary_factor
    if not 0 < cfg.partial_rotary_factor <= 1 or rot != int(rot) \
            or int(rot) % 2:
        raise ValueError(
            f"partial_rotary_factor={cfg.partial_rotary_factor} of a head "
            f"of {cfg.resolved_head_dim} must give an even number of "
            f"rotary dims in (0, head_dim]")
    for key, want in (("norm_topk_prob", True), ("n_routed_experts", 0),
                      ("n_shared_experts", 0), ("first_k_dense_replace", 0),
                      ("routed_scaling_factor", 1.0),
                      ("sandwich_norm", False)):
        if getattr(cfg, key) != want:
            raise ValueError(f"the hybrid with expert layers is built with "
                             f"{key}={want!r} only, got "
                             f"{getattr(cfg, key)!r}")


def param_count(cfg) -> int:
    h, hd = cfg.hidden_size, cfg.resolved_head_dim
    nq, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    F, Fs = cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size
    common = 2 * h + h * cfg.router_width + 3 * h * F * cfg.num_experts \
        + 3 * h * Fs + h
    full = common + h * hd * (3 * nq + 2 * nkv) + 2 * hd
    linear = common + G.linear_mixer_param_count(cfg)
    n_full = layer_kinds(cfg).count("full")
    return n_full * full + (cfg.num_hidden_layers - n_full) * linear \
        + 2 * cfg.vocab_size * h + h


# ------------------------------------------------------------------- init

def init_params(key: jax.Array, cfg) -> dict:
    """``transformer.init_params`` for this block: truncated normal 0.02,
    the projections back into the residual stream scaled by
    1/sqrt(2 . layers), the zero-centred norms at 0, the linear mixer's
    leaves as ``gdn_hybrid.linear_mixer_params`` draws them."""
    h, hd = cfg.hidden_size, cfg.resolved_head_dim
    nq, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    E, F = cfg.num_experts, cfg.moe_intermediate_size
    Fs = cfg.shared_expert_intermediate_size
    out_std = 0.02 / math.sqrt(2 * cfg.num_hidden_layers)
    keys = iter(jax.random.split(key, 2 + 20 * cfg.num_hidden_layers))

    def tn(shape, std=0.02):
        return (std * jax.random.truncated_normal(
            next(keys), -2, 2, shape, jnp.float32)).astype(cfg.dtype)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

    zeros = lambda *shape: jnp.zeros(shape, cfg.dtype)  # noqa: E731

    def layer(kind):
        out = {"input_norm": zeros(h), "post_attn_norm": zeros(h),
               "w_router": tn((h, cfg.router_width)),
               "we_gate": tn((E, h, F)), "we_up": tn((E, h, F)),
               "we_down": tn((E, F, h), out_std),
               "ws_gate": tn((h, Fs)), "ws_up": tn((h, Fs)),
               "ws_down": tn((Fs, h), out_std), "ws_sigmoid": tn((h, 1))}
        if kind == "full":
            return {**out, "wq": tn((h, nq * 2 * hd)),
                    "wk": tn((h, nkv * hd)), "wv": tn((h, nkv * hd)),
                    "wo": tn((nq * hd, h), out_std),
                    "q_norm": zeros(hd), "k_norm": zeros(hd)}
        return {**out, **G.linear_mixer_params(cfg, tn, uniform, out_std)}

    return {
        "embed": tn((cfg.vocab_size, h)),
        "layers": tuple(layer(kind) for kind in layer_kinds(cfg)),
        "final_norm": zeros(h),
        "lm_head": tn((h, cfg.vocab_size)),
    }


# ------------------------------------------------- what the block brings

def norm(x, w, cfg):
    """The zero-centred RMSNorm: ``x / rms(x) * (1 + w)``."""
    from .transformer import rms_norm
    return rms_norm(x, 1.0 + w.astype(jnp.float32), cfg.rms_norm_eps)


def rope_tables(positions, cfg):
    """cos, sin (B, S, rot / 2) float32 of the absolute ``positions``
    (B, S), over the rotary dims alone."""
    return M.position_tables(positions, rotary_dim(cfg), cfg.rope_theta)


def _partial_rope(x, rope, rot: int):
    """x (B, S, n, hd): dims 0 .. rot - 1 of every head rotated
    (split-half), the rest as they are."""
    return jnp.concatenate([M._rope(x[..., :rot], *rope), x[..., rot:]],
                           axis=-1)


def mixer_input(x, layer, *, cfg):
    """What a mixer reads: ``norm(x; input_norm)``."""
    return norm(x, layer["input_norm"], cfg)


def attention_qkv(r, layer, *, cfg, rope):
    """``q`` (B, S, n, hd), ``k``, ``v`` (B, S, n_kv, hd) and the heads'
    output ``gate`` (B, S, n, hd), from the normed rows ``r``: projections
    (a head's query and gate side by side in ``wq``), per-head zero-centred
    norms of q and k, the rotary embedding over their leading dims."""
    from .transformer import _dense
    B, S, _ = r.shape
    hd, rot = cfg.resolved_head_dim, rotary_dim(cfg)
    dense = _dense(cfg)
    qg = dense(r, layer["wq"]).reshape(B, S, cfg.num_attention_heads, 2 * hd)
    k = dense(r, layer["wk"]).reshape(B, S, cfg.num_key_value_heads, hd)
    v = dense(r, layer["wv"]).reshape(B, S, cfg.num_key_value_heads, hd)
    q = _partial_rope(norm(qg[..., :hd], layer["q_norm"], cfg), rope, rot)
    k = _partial_rope(norm(k, layer["k_norm"], cfg), rope, rot)
    return q, k, v, qg[..., hd:]


def gate_heads(attn, gate):
    """The heads' outputs ``attn`` (B, S, ..heads.., hd), each times
    ``sigmoid`` of its ``gate`` (B, S, n, hd), in float32 (also
    ``models/swa_moe.py``'s output gate)."""
    return attn.astype(jnp.float32).reshape(gate.shape) \
        * jax.nn.sigmoid(gate.astype(jnp.float32))


def attention_output(attn, gate, x, layer, *, cfg):
    """The heads' outputs ``attn`` (B, S, ..heads.., hd) float32, each
    gated by ``sigmoid(gate)``, through ``wo`` onto the residual stream:
    ``h``."""
    from .transformer import _dense
    B, S = attn.shape[:2]
    a = gate_heads(attn, gate)
    return x + _dense(cfg)(a.astype(x.dtype).reshape(B, S, -1), layer["wo"])


def linear_mixer_output(o, r, x, layer, *, cfg):
    """A linear layer's ``h`` from the recurrence's outputs ``o``."""
    return x + G.linear_output(o, r, layer, cfg=cfg)


def mlp(h, layer, *, cfg, valid=None):
    """``y = h + MoE(norm(h; post_attn_norm))`` and the expert layer's
    ``mla_moe.moe_counts`` of the rows ``valid`` marks."""
    m, counts = M.expert_mlp(norm(h, layer["post_attn_norm"], cfg), layer,
                             cfg=cfg, valid=valid)
    return h + m, counts


def final_norm(x, params, cfg):
    return norm(x, params["final_norm"], cfg)
