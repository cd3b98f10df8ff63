"""Compressed convolutional attention over a top-1 expert layer with an MLP
router and no shared expert (the layer ZAYA1-8B publishes, ``model_type``
``zaya``), for the serving engine's paged layer body and for the cache-less
``transformer.forward``.

It holds what DIFFERS from the blocks that are there and copies none of
them: the expert layer (routing over the router's whole width, the held
experts' part of the routed sum, the counters) is ``models/mla_moe.py``'s as
it stands (``mla_moe.expert_mlp``), handed this block's router LOGITS and
told not to renormalise a chosen score (``NORM_TOPK_PROB``); the partial
rotary embedding is ``models/gdn_moe.py``'s (``_partial_rope``).  Written
here: the attention's latents, their two causal convolutions, the q-k mean,
the value shift, the L2 norms with a temperature a KV head, the router's
MLP, and the composition.

``x`` (S, H) is the residual stream; every RMSNorm has ``rms_norm_eps``
(weight initialised 1); ``n`` query heads and ``n_kv`` KV heads of ``hd``,
``C = (n + n_kv) hd`` latent channels in ``G = n + n_kv`` groups of ``hd``
(the query heads, then the key heads); ``z_{-1} = z_{-2} = r_{-1} = 0``::

    r   = norm(x; ln1)
    z   = [r wq | r wk]                                 latents (S, C)
    u_t = a0 . z_{t-1} + a1 . z_t + b0                  stage 0: depthwise, kernel cca_time0 = 2
    y_t[g] = u_{t-1}[g] A0[g] + u_t[g] A1[g] + b1[g]    stage 1: by head, kernel cca_time1 = 2
                                          the sequence is padded ONCE, with two
                                          zero rows before stage 0: u_{-1} = b0
    m_q[h] = (z_q[h] + z_k[h // (n / n_kv)]) / 2        the q-k mean, of the latents
    m_k[j] = mean of m_q[h] over the query heads h of KV head j
    q' = y_q + m_q,   k' = y_k + m_k
    v_t = [r_t wv1 | r_{t-1} wv2]         viewed (n_kv, hd): KV head 0 the token's
                                          own values, KV head 1 the PREVIOUS token's
    q'' = sqrt(hd) q' / |q'|,   k''_j = tau_j sqrt(hd) k'_j / |k'_j|     float32, a head;
                                          1e-6 under each norm; tau_j = k_temp[j] > 0
    q, k = rope(q''), rope(k'')           split-half over the first partial_rotary_factor
                                          of a head's dims, theta rope_theta, no scaling
    o   = causal softmax(q k^T / sqrt(hd)) v,  n / n_kv query heads a KV head
    h   = x + o wo
    r2  = norm(h; ln2)
    p   = softmax(gelu(gelu(r2 wr_down . wr_1 + br_1) wr_2 + br_2) wr_3 + br_3)
                                          float32 under "highest"; erf GELU; over
                                          all ``router_width`` experts
    e   = argmax p;   x' = h + p_e . SwiGLU_e(r2)       the weight is p_e ITSELF

The published ``num_experts_per_tok`` is 1 and ``norm_topk_prob`` False: a
renormalised top-1 weight would be 1 whatever the router says.  This program
HOLDS ``num_experts`` of the router's ``router_width`` experts (ids
``expert_offset`` onwards; the benchmark's configuration holds all 16);
what an absent expert would add is left out.  No shared expert, no post-MLP
norm.  ``logits = norm(x_L; final_norm) embed^T`` (tied).

What a request caches in one layer, the kind ``"conv_full"``
(``serving/kv_pool.py``): ``k`` and ``v`` as rows of ``(n_kv, hd)`` in
whole-context pages, AND in its batch slot a TAIL of ``tail_shape`` =
``(2 C + hd,)`` elements of ``cfg.dtype``: ``[z_{t-2} | z_{t-1} | r_{t-1}
wv2]``, what the two convolutions and the value shift need of the rows
before the next one.  A request's first rows start from a tail of zeros.

Parameter tree: ``embed`` (V, H), ``final_norm`` (H,) and ``layers``, a
tuple of one dict a layer: ``ln1``, ``w_qkv`` (H, C + 2 hd): the four
published projections ``[wq | wk | wv1 | wv2]`` as the columns of one
matrix (one product a layer), ``conv0_w`` (2, C), ``conv0_b`` (C,),
``conv1_w`` (G, 2, hd, hd): ``A0[g]``, ``A1[g]``, ``conv1_b`` (C,),
``k_temp`` (n_kv,) float32, ``wo`` (n hd, H), ``ln2``, the router's
``wr_down`` (H, R), ``wr_1``, ``wr_2`` (R, R), ``br_1``, ``br_2`` (R,),
``wr_3`` (R, router width), ``br_3`` (router width,), and the held experts'
``we_gate`` / ``we_up`` (E, H, F), ``we_down`` (E, F, H).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from ..utils.profiling import scope
from . import mla_moe as M
from .gdn_hybrid import (attention_scale, embed,  # noqa: F401
                         final_norm)
from .gdn_moe import _partial_rope, rotary_dim
from .mla_moe import COUNTS_FROM_ZERO, NOPE_KINDS  # noqa: F401

#: what the engine counts for this block in ``stats``, all summed on the
#: device through a burst: the expert layers' four, then the decode rows
#: whose tail was live (read and rewritten), ``conv_tail_slot_steps``
COUNTERS = M.COUNTERS + ("conv_tail_slot_steps",)
DEVICE_COUNTERS = COUNTERS

#: how this block's router scores an expert (``mla_moe.route``): a softmax
#: over the router's whole width, of the logits :func:`router_logits` makes,
#: and the chosen score is the weight as it is
ROUTER_SCORING = "softmax"
NORM_TOPK_PROB = False

#: the scope the engine opens round this block's paged attention beneath
#: ``attn_core`` (``profiling.ATTENTION_SUBSCOPES``)
PAGED_ATTENTION_SCOPE = "attn_paged"

#: under each L2 norm of a head's query or key
L2_EPS = 1e-6

#: what ``init_params`` sets every KV head's temperature to (a learned
#: scalar's constant start).  At 1.5 and under, greedy decoding of the block
#: at random weights and the published widths falls into repeating one
#: token (a head with a thousand keys behind it averages them), and a fault
#: in the conv tail or the value shift then changes nothing that is served
K_TEMP_INIT = 2.5


def refuse(cfg, what: str):
    raise NotImplementedError(
        f"the compressed convolutional attention block with a top-1 expert "
        f"layer (cca_time0={cfg.cca_time0}, cca_time1={cfg.cca_time1}, "
        f"num_experts={cfg.num_experts} of {cfg.router_width} held) is "
        f"served by serving/engine.py and run cache-less by models/"
        f"transformer.forward only; {what} is not built for it (ROADMAP: "
        f"mechanisms the system cannot run yet)")


def check_config(cfg) -> None:
    """Called from ``TransformerConfig.__post_init__`` when the block is
    selected: the block is what the module docstring writes down, and a
    field that asks for another variant is refused by name."""
    need = ("moe_intermediate_size", "router_width", "num_experts",
            "router_hidden_size")
    missing = [k for k in need if getattr(cfg, k) <= 0]
    if missing:
        raise ValueError(f"cca_time0={cfg.cca_time0} selects the compressed "
                         f"convolutional attention block, which also needs "
                         f"{missing} > 0")
    M.check_held_experts(cfg)
    if cfg.num_attention_heads % cfg.num_key_value_heads:
        raise ValueError("num_attention_heads must be a multiple of "
                         "num_key_value_heads")
    rot = cfg.resolved_head_dim * cfg.partial_rotary_factor
    if not 0 < cfg.partial_rotary_factor <= 1 or rot != int(rot) \
            or int(rot) % 2:
        raise ValueError(
            f"partial_rotary_factor={cfg.partial_rotary_factor} of a head "
            f"of {cfg.resolved_head_dim} must give an even number of "
            f"rotary dims in (0, head_dim]")
    # the published config has no dense MLP width; a top-1 weight is the
    # router's probability itself; the value shift needs a KV head each for
    # the token's own values and the previous token's
    for key, want in (("cca_time0", 2), ("cca_time1", 2),
                      ("num_key_value_heads", 2), ("intermediate_size", None),
                      ("num_experts_per_tok", 1), ("norm_topk_prob", False),
                      ("routed_scaling_factor", 1.0),
                      ("tie_word_embeddings", True), ("nope_interval", 0),
                      ("n_experts", 0), ("n_routed_experts", 0),
                      ("num_local_experts", 0), ("kv_lora_rank", 0),
                      ("linear_key_head_dim", 0), ("sliding_window", 0),
                      ("mamba_d_state", 0), ("attention_impl", "xla")):
        if getattr(cfg, key) != want:
            raise ValueError(f"the compressed convolutional attention block "
                             f"is built with {key}={want!r} only, got "
                             f"{getattr(cfg, key)!r}")


def layer_kinds(cfg) -> tuple[str, ...]:
    """Every layer caches K/V rows in whole-context pages AND holds a tail
    a slot."""
    return ("conv_full",) * cfg.num_hidden_layers


def latent_channels(cfg) -> int:
    """``C``: the channels the two convolutions run over, a head's ``hd``
    for every query head and then every KV head."""
    return (cfg.num_attention_heads + cfg.num_key_value_heads) \
        * cfg.resolved_head_dim


def tail_shape(cfg) -> tuple[int]:
    """One slot's tail in one layer (``cfg.dtype``): ``[z_{t-2} | z_{t-1} |
    r_{t-1} wv2]``."""
    return (2 * latent_channels(cfg) + cfg.resolved_head_dim,)


def param_count(cfg) -> int:
    h, hd, C = cfg.hidden_size, cfg.resolved_head_dim, latent_channels(cfg)
    G, R = C // hd, cfg.router_hidden_size
    attn = h * (C + 2 * hd) + cfg.num_attention_heads * hd * h \
        + 4 * C + 2 * G * hd * hd + cfg.num_key_value_heads
    router = h * R + 2 * (R * R + R) + R * cfg.router_width \
        + cfg.router_width
    experts = 3 * h * cfg.moe_intermediate_size * cfg.num_experts
    return cfg.num_hidden_layers * (attn + router + experts + 2 * h) \
        + cfg.vocab_size * h + h


# ------------------------------------------------------------------- init

def init_params(key: jax.Array, cfg) -> dict:
    """``transformer.init_params`` for this block: truncated normal 0.02
    (the router's four matrices too), the projections back into the
    residual stream scaled by 1/sqrt(2 . layers), norms at one, the
    router's biases at ZERO (drawn at 0.02 they outweigh what three small
    layers leave of a token in the logits, and two experts in sixteen get
    every token); the convolutions' weights and biases uniform in
    +-1/sqrt(fan-in) (fan-in 2 for the depthwise stage, 2 hd for the stage
    grouped by head); ``k_temp`` at ``K_TEMP_INIT`` in float32.  No
    ``lm_head``: tied."""
    h, hd, C = cfg.hidden_size, cfg.resolved_head_dim, latent_channels(cfg)
    nq, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    G, R, W = C // hd, cfg.router_hidden_size, cfg.router_width
    E, F = cfg.num_experts, cfg.moe_intermediate_size
    out_std = 0.02 / math.sqrt(2 * cfg.num_hidden_layers)
    keys = iter(jax.random.split(key, 1 + 14 * cfg.num_hidden_layers))

    def tn(shape, std=0.02, dtype=cfg.dtype):
        return (std * jax.random.truncated_normal(
            next(keys), -2, 2, shape, jnp.float32)).astype(dtype)

    def uniform(shape, bound, dtype=cfg.dtype):
        return jax.random.uniform(next(keys), shape, jnp.float32, -bound,
                                  bound).astype(dtype)

    ones = lambda *shape: jnp.ones(shape, cfg.dtype)  # noqa: E731
    zeros = lambda *shape: jnp.zeros(shape, cfg.dtype)  # noqa: E731

    def layer():
        return {
            "ln1": ones(h), "w_qkv": tn((h, C + 2 * hd)),
            "conv0_w": uniform((2, C), 2 ** -0.5),
            "conv0_b": uniform((C,), 2 ** -0.5),
            "conv1_w": uniform((G, 2, hd, hd), (2 * hd) ** -0.5),
            "conv1_b": uniform((C,), (2 * hd) ** -0.5),
            "k_temp": jnp.full((nkv,), K_TEMP_INIT, jnp.float32),
            "wo": tn((nq * hd, h), out_std), "ln2": ones(h),
            "wr_down": tn((h, R)), "wr_1": tn((R, R)), "br_1": zeros(R),
            "wr_2": tn((R, R)), "br_2": zeros(R), "wr_3": tn((R, W)),
            "br_3": zeros(W),
            "we_gate": tn((E, h, F)), "we_up": tn((E, h, F)),
            "we_down": tn((E, F, h), out_std)}

    return {
        "embed": tn((cfg.vocab_size, h)),
        "layers": tuple(layer() for _ in range(cfg.num_hidden_layers)),
        "final_norm": ones(h),
    }


# ------------------------------------------------- what the block brings

def rope_tables(positions, cfg):
    """cos, sin (B, S, rot / 2) float32 of the absolute ``positions``
    (B, S), over the rotary dims alone."""
    return M.position_tables(positions, rotary_dim(cfg), cfg.rope_theta)


def mixer_input(x, layer, *, cfg):
    """What the mixer reads: ``norm(x; ln1)``."""
    from .transformer import rms_norm
    return rms_norm(x, layer["ln1"], cfg.rms_norm_eps)


def _l2_scaled(x, hd: int):
    """``sqrt(hd) x / |x|`` over a head, float32."""
    return x * (math.sqrt(hd) * lax.rsqrt(
        jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS))


def attention_qkv(r, layer, *, cfg, rope, tail, valid):
    """``q`` (B, S, n, hd), ``k``, ``v`` (B, S, n_kv, hd) from the normed
    rows ``r`` (B, S, H) CONTINUED from ``tail`` (B,) + ``tail_shape``, what
    the rows before ``r``'s first left (zeros before a request's first):
    the module docstring's latents, convolutions, q-k mean, value shift,
    norms and rotary embedding.  Also None (no output gate) and the NEW
    tail: what the ``n`` leading rows that ``valid`` (B, S) marks leave, so
    padding after a prompt's end never enters it and a row with no valid
    row hands its tail back bit for bit."""
    from .transformer import _dense
    B, S, _ = r.shape
    hd, C = cfg.resolved_head_dim, latent_channels(cfg)
    nq, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    rep, G = nq // nkv, C // hd
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    zv = _dense(cfg)(r, layer["w_qkv"])
    z, v1, v2 = zv[..., :C], zv[..., C:C + hd], zv[..., C + hd:]
    with scope("cca_conv"):
        # rows -2 .. S - 1 of the latents, rows -1 .. S - 1 of ``r wv2``
        ze = jnp.concatenate([tail[:, :2 * C].reshape(B, 2, C), z], axis=1)
        ve = jnp.concatenate([tail[:, None, 2 * C:], v2], axis=1)
        w0, w1 = f32(layer["conv0_w"]), layer["conv1_w"]
        # stage 0 at rows -1 .. S - 1, stage 1 at rows 0 .. S - 1
        u = (w0[0] * f32(ze[:, :-1]) + w0[1] * f32(ze[:, 1:])
             + f32(layer["conv0_b"])).astype(r.dtype).reshape(B, S + 1, G, hd)
        y = jnp.einsum("bsgc,gcd->bsgd",
                       jnp.concatenate([u[:, :-1], u[:, 1:]], axis=-1),
                       w1.reshape(G, 2 * hd, hd),
                       preferred_element_type=jnp.float32) \
            + f32(layer["conv1_b"]).reshape(G, hd)
        zq = f32(z[..., :nq * hd]).reshape(B, S, nkv, rep, hd)
        zk = f32(z[..., nq * hd:]).reshape(B, S, nkv, 1, hd)
        mq = 0.5 * (zq + zk)
        q = y[:, :, :nq] + mq.reshape(B, S, nq, hd)
        k = y[:, :, nq:] + jnp.mean(mq, axis=3)
        v = jnp.stack([v1, ve[:, :-1]], axis=2)
        # what the valid rows leave: the last two latents and the last
        # ``r wv2`` that end at row ``n``
        n_valid = jnp.sum(valid.astype(jnp.int32), axis=1)

        def cut(a, size):
            return jax.vmap(lambda row, n: lax.dynamic_slice_in_dim(
                row, n, size))(a, n_valid)

        new_tail = jnp.concatenate(
            [cut(ze, 2).reshape(B, 2 * C), cut(ve, 1)[:, 0]],
            axis=-1).astype(tail.dtype)
    q = _l2_scaled(q, hd)
    k = _l2_scaled(k, hd) * f32(layer["k_temp"])[:, None]
    rot = rotary_dim(cfg)
    return (_partial_rope(q, rope, rot).astype(r.dtype),
            _partial_rope(k, rope, rot).astype(r.dtype), v, None, new_tail)


def attention_output(attn, gate, x, layer, *, cfg):
    """The heads' outputs ``attn`` (B, S, ..heads.., hd) through ``wo`` onto
    the residual stream: ``h`` (``gate``: the heads' output gate, which
    this block has not)."""
    from .transformer import _dense
    B, S = attn.shape[:2]
    return x + _dense(cfg)(attn.astype(x.dtype).reshape(B, S, -1),
                           layer["wo"])


def router_logits(rows, layer):
    """The router's MLP on the normed rows (T, H): logits (T, router
    width), float32 under "highest" (``mla_moe.expert_mlp`` calls it under
    ``moe_route`` for a block that brings it)."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    gelu = lambda a: jax.nn.gelu(a, approximate=False)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        s = f32(rows) @ f32(layer["wr_down"])
        t = gelu(s @ f32(layer["wr_1"]) + f32(layer["br_1"]))
        t = gelu(t @ f32(layer["wr_2"]) + f32(layer["br_2"]))
        return t @ f32(layer["wr_3"]) + f32(layer["br_3"])


def mlp(h, layer, *, cfg, valid=None):
    """``x' = h + p_e . SwiGLU_e(norm(h; ln2))`` and the expert layer's
    ``mla_moe.moe_counts`` of the rows ``valid`` marks."""
    from .transformer import rms_norm
    m, counts = M.expert_mlp(rms_norm(h, layer["ln2"], cfg.rms_norm_eps),
                             layer, cfg=cfg, valid=valid)
    return h + m, counts


# ------------------------------------------------- the cache-less forward

def hidden_states(params, input_ids, cfg):
    """(B, S) ids -> final-norm hidden states (B, S, H): the whole
    sequence at once from a tail of zeros, materialised attention under a
    causal mask; no cache."""
    B, S = input_ids.shape
    hd = cfg.resolved_head_dim
    rep = cfg.num_attention_heads // cfg.num_key_value_heads
    with scope("embed"):
        x = embed(params, input_ids, cfg)
        rope = rope_tables(jnp.arange(S)[None, :], cfg)
    causal = jnp.tril(jnp.ones((S, S), jnp.bool_))
    tail = jnp.zeros((B,) + tail_shape(cfg), cfg.dtype)
    valid = jnp.ones((B, S), jnp.bool_)
    for layer in params["layers"]:
        with scope("attn_qkv"):
            q, k, v, _, _ = attention_qkv(
                mixer_input(x, layer, cfg=cfg), layer, cfg=cfg, rope=rope,
                tail=tail, valid=valid)
        with scope("attn_core"):
            qg = q.reshape(B, S, -1, rep, hd)
            s = jnp.einsum("bsgrh,bkgh->bgrsk", qg, k,
                           preferred_element_type=jnp.float32) / math.sqrt(hd)
            p = jax.nn.softmax(jnp.where(causal, s, -1e30), axis=-1)
            o = jnp.einsum("bgrsk,bkgh->bsgrh", p.astype(v.dtype), v,
                           preferred_element_type=jnp.float32)
        with scope("attn_out"):
            x = attention_output(o, None, x, layer, cfg=cfg)
        with scope("mlp"):
            x, _ = mlp(x, layer, cfg=cfg)
    with scope("loss_head"):
        return final_norm(x, params, cfg)
