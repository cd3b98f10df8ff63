"""A dense stack whose layers run several times with the same weights (the
looped language model Ouro-2.6B publishes, ``model_type`` ``ouro``), for the
serving engine's paged layer body and for the cache-less
``transformer.forward``.

Every token runs the SAME ``num_hidden_layers`` weight layers
``total_ut_steps`` = T times, a PASS after a pass; each pass has K/V of its
own in every layer, so a token caches ``T x num_hidden_layers`` rows and
cache ``p = t . L + l`` is read and written with the weights of layer ``l``
(``serving/kv_pool.py`` counts caches, ``serving/engine.
_paged_block_forward`` walks them).  ``x`` (S, H) is the residual stream;
every RMSNorm has ``rms_norm_eps`` (weight initialised 1); ``n`` query heads
and ``n_kv`` KV heads of ``hd`` (the published model: 16 and 16, plain
multi-head attention)::

    x = E[id]
    for t in 0 .. T-1:
      for l in 0 .. L-1:
        r = norm(x; ln1_l)
        q, k, v = rope(r wq), rope(r wk), r wv       split-half over the whole head,
                                                     theta rope_theta; no bias, no q/k norm
        a = causal softmax(q k^T / sqrt(hd)) v . wo  over pass t's keys and values ALONE
        x = x + norm(a; post_attn_norm_l)            sandwich: the sublayer's OUTPUT is normed too
        m = w_down (silu(w_gate r2) * (w_up r2)),    r2 = norm(x; ln2_l)
        x = x + norm(m; post_mlp_norm_l)
      x = norm(x; final_norm)                        the ONE final norm, at the end of EVERY pass;
      h_t = x                                        the normed state is what pass t+1 starts from
      lam_t = sigmoid(w_exit . h_t + b_exit)         float32
    p_t = lam_t prod_{j<t} (1 - lam_j)  (t < T-1);   p_{T-1} = prod_{j<T-1} (1 - lam_j)
    e = min{t : p_0 + ... + p_t >= early_exit_threshold}, T-1 where no sum reaches it
    logits = h_e lm_head

Every pass of every token runs whatever ``e`` is: a later token's pass ``t``
attends to this token's pass-``t`` keys.  The gate chooses which pass's
state reaches the head, ONCE a row; it saves no compute.  At the published
threshold 1 the sum reaches 1 at the last pass only.

What a request caches: kind ``"full"`` in every (pass, layer), K and V rows
of ``(n_kv, hd)`` in whole-context pages, addressed by the ONE page table
its grant fills.

Parameter tree: ``embed`` (V, H), ``lm_head`` (H, V) (untied),
``final_norm`` (H,), ``exit_gate`` ``{"w": (H,), "b": (1,)}`` and
``layers``, a tuple of one dict a WEIGHT layer: ``ln1``, ``w_qkv`` (H,
(n + 2 n_kv) hd) = ``[wq | wk | wv]`` as the columns of one matrix (one
product a layer a pass), ``wo`` (n hd, H), ``post_attn_norm``, ``ln2``,
``w_gate`` / ``w_up`` (H, F), ``w_down`` (F, H), ``post_mlp_norm``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..utils.profiling import scope
from . import mla_moe as M
from .cca_moe import mixer_input  # what the mixer reads: norm(x; ln1)
from .gdn_hybrid import attention_scale, embed  # noqa: F401

#: what the engine counts for this block in ``stats``, all summed on the
#: device through a burst over the rows a decode step samples: passes run
#: (``total_ut_steps`` a row), the 1-based pass whose state reached the head,
#: and the rows whose state was not the last pass's
COUNTERS = ("ut_passes", "exit_step_sum", "early_exit_rows")
DEVICE_COUNTERS = COUNTERS
#: no expert layer: nothing to start a sum of ``mla_moe.moe_counts`` from
COUNTS_FROM_ZERO = False
#: every layer's queries and keys rotate
NOPE_KINDS = ()
#: the module's own tables: the engine's, over the whole head
rope_tables = None

#: the scope the engine opens round this block's paged attention beneath
#: ``attn_core`` (``profiling.ATTENTION_SUBSCOPES``)
PAGED_ATTENTION_SCOPE = "attn_paged"

#: a request's pages hold, in every cache, rows that depend on the tokens
#: before them alone and sit at the same (page, offset) under ONE table: a
#: shared prompt prefix is shared in all ``T x L`` caches at once
PREFIX_CACHE = True


def refuse(cfg, what: str):
    raise NotImplementedError(
        f"the looped dense block (total_ut_steps={cfg.total_ut_steps} passes "
        f"over {cfg.num_hidden_layers} layers, K/V pages a pass) is served "
        f"by serving/engine.py and run cache-less by models/transformer."
        f"forward only; {what} is not built for it (ROADMAP: mechanisms the "
        f"system cannot run yet)")


def check_config(cfg) -> None:
    """Called from ``TransformerConfig.__post_init__`` when the block is
    selected: the block is what the module docstring writes down, and a
    field that asks for another variant is refused by name."""
    if cfg.total_ut_steps < 1:
        raise ValueError(f"total_ut_steps={cfg.total_ut_steps} must be >= 1")
    if not 0.0 < cfg.early_exit_threshold <= 1.0:
        raise ValueError(
            f"early_exit_threshold={cfg.early_exit_threshold} must lie in "
            f"(0, 1]: it is held against a sum of probabilities")
    if cfg.num_attention_heads % cfg.num_key_value_heads:
        raise ValueError("num_attention_heads must be a multiple of "
                         "num_key_value_heads")
    if not cfg.intermediate_size or cfg.intermediate_size <= 0:
        raise ValueError("the looped dense block has a SwiGLU of "
                         "intermediate_size > 0 in every layer")
    for key, want in (("tie_word_embeddings", False), ("nope_interval", 0),
                      ("n_experts", 0), ("num_experts", 0),
                      ("n_routed_experts", 0), ("num_local_experts", 0),
                      ("kv_lora_rank", 0), ("linear_key_head_dim", 0),
                      ("sliding_window", 0), ("mamba_d_state", 0),
                      ("cca_time0", 0), ("partial_rotary_factor", 1.0),
                      ("logits_scaling", 1.0), ("attention_impl", "xla")):
        if getattr(cfg, key) != want:
            raise ValueError(f"the looped dense block is built with "
                             f"{key}={want!r} only, got "
                             f"{getattr(cfg, key)!r}")


def layer_kinds(cfg) -> tuple[str, ...]:
    """One entry a WEIGHT layer; a pass walks them all, and every (pass,
    layer) is a cache of the layer's kind (``cfg.layer_passes``)."""
    return ("full",) * cfg.num_hidden_layers


def layer_param_count(cfg) -> int:
    h, hd = cfg.hidden_size, cfg.resolved_head_dim
    nq, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    return h * (nq + 2 * nkv) * hd + nq * hd * h \
        + 3 * h * cfg.intermediate_size + 4 * h


def param_count(cfg) -> int:
    h = cfg.hidden_size
    return cfg.num_hidden_layers * layer_param_count(cfg) \
        + 2 * cfg.vocab_size * h + h + (h + 1)


# ------------------------------------------------------------------- init

def init_params(key: jax.Array, cfg) -> dict:
    """``transformer.init_params`` for this block: truncated normal 0.02
    (embedding, head and the gate's weight too), norms at one, the gate's
    bias at zero.  The projections back into the residual stream are NOT
    scaled down by depth: their outputs are normed before they are added."""
    h, hd, F = cfg.hidden_size, cfg.resolved_head_dim, cfg.intermediate_size
    nq, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    keys = iter(jax.random.split(key, 3 + 5 * cfg.num_hidden_layers))

    def tn(shape):
        return (0.02 * jax.random.truncated_normal(
            next(keys), -2, 2, shape, jnp.float32)).astype(cfg.dtype)

    ones = lambda: jnp.ones((h,), cfg.dtype)  # noqa: E731

    def layer():
        return {
            "ln1": ones(), "w_qkv": tn((h, (nq + 2 * nkv) * hd)),
            "wo": tn((nq * hd, h)), "post_attn_norm": ones(), "ln2": ones(),
            "w_gate": tn((h, F)), "w_up": tn((h, F)), "w_down": tn((F, h)),
            "post_mlp_norm": ones()}

    return {
        "embed": tn((cfg.vocab_size, h)),
        "lm_head": tn((h, cfg.vocab_size)),
        "exit_gate": {"w": tn((h,)), "b": jnp.zeros((1,), cfg.dtype)},
        "layers": tuple(layer() for _ in range(cfg.num_hidden_layers)),
        "final_norm": ones(),
    }


# ------------------------------------------------- what the block brings

def attention_qkv(r, layer, *, cfg, rope=None):
    """``q`` (B, S, n, hd), ``k``, ``v`` (B, S, n_kv, hd) from the normed
    rows ``r``: ONE product with ``w_qkv``, queries and keys rotated; and
    the heads' output gate, which this block has not (None)."""
    from .transformer import _dense
    B, S, _ = r.shape
    hd = cfg.resolved_head_dim
    nq, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    q, k, v = jnp.split(_dense(cfg)(r, layer["w_qkv"]),
                        [nq * hd, (nq + nkv) * hd], axis=-1)
    q = q.reshape(B, S, nq, hd)
    k = k.reshape(B, S, nkv, hd)
    return M._rope(q, *rope), M._rope(k, *rope), v.reshape(B, S, nkv, hd), \
        None


def attention_output(attn, gate, x, layer, *, cfg):
    """The heads' outputs ``attn`` (B, S, ..heads.., hd) float32 through
    ``wo``, normed, onto the residual stream: ``h``."""
    from .transformer import _dense, rms_norm
    B, S = attn.shape[:2]
    a = _dense(cfg)(attn.astype(x.dtype).reshape(B, S, -1), layer["wo"])
    return x + rms_norm(a, layer["post_attn_norm"], cfg.rms_norm_eps)


def mlp(h, layer, *, cfg, valid=None):
    """``x' = h + norm(SwiGLU(norm(h; ln2)); post_mlp_norm)``; and the
    layer's device-side counters, which a dense MLP has not (None)."""
    from .transformer import _dense, rms_norm
    dense, eps = _dense(cfg), cfg.rms_norm_eps
    r = rms_norm(h, layer["ln2"], eps)
    m = dense(jax.nn.silu(dense(r, layer["w_gate"]))
              * dense(r, layer["w_up"]), layer["w_down"])
    return h + rms_norm(m, layer["post_mlp_norm"], eps), None


def pass_end(x, params, t: int, exits, *, cfg):
    """The end of pass ``t`` (static) on the residual stream ``x`` (B, S,
    H): the model's final norm (what pass ``t + 1`` starts from, and what
    the head would read), the exit gate, and the running choice of the state
    that reaches the head.  ``exits`` is None before pass 0 and afterwards
    ``(chosen (B, S, H), cum, survive (B, S) float32, step (B, S) int32)``:
    the state of the first pass whose cumulated exit probability reached
    ``early_exit_threshold`` (the last pass's where none has: ``step`` is
    then still ``total_ut_steps - 1``), the probability cumulated so far,
    the probability of having passed every gate so far, and the chosen
    pass.  One state a row is kept, never ``T``."""
    from .transformer import rms_norm
    T = cfg.total_ut_steps
    h = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    if exits is None:
        shape = h.shape[:2]
        exits = (h, jnp.zeros(shape, jnp.float32),
                 jnp.ones(shape, jnp.float32),
                 jnp.full(shape, T - 1, jnp.int32))
    chosen, cum, survive, step = exits
    gate = params["exit_gate"]
    lam = jax.nn.sigmoid(
        jnp.sum(h.astype(jnp.float32) * gate["w"].astype(jnp.float32), -1)
        + gate["b"].astype(jnp.float32)[0])
    p = survive if t == T - 1 else lam * survive
    undecided = cum < cfg.early_exit_threshold
    cum = cum + p
    # this pass is chosen where the sum reaches the threshold now, and at
    # the last pass wherever nothing was chosen before
    now = undecided & ((cum >= cfg.early_exit_threshold) | (t == T - 1))
    chosen = jnp.where(now[..., None], h, chosen)
    step = jnp.where(now, t, step)
    return h, (chosen, cum, survive * (1.0 - lam), step)


def exit_choice(exits, valid, *, cfg):
    """After the last pass: the chosen state a row ``h_e`` (B, S, H), ALREADY
    normed (:func:`final_norm` on the seam leaves it alone), and this call's
    ``DEVICE_COUNTERS`` over the rows ``valid`` (B, S) marks."""
    chosen, _, _, step = exits
    T = cfg.total_ut_steps
    v = valid.astype(jnp.int32)
    return chosen, jnp.stack([
        T * jnp.sum(v), jnp.sum(v * (step + 1)),
        jnp.sum(v * (step < T - 1))]).astype(jnp.int32)


def final_norm(x, params, cfg):
    """The seam's final norm (``engine._all_logits``): the loop hands over
    ``h_e``, which :func:`pass_end` normed; it is not normed twice."""
    return x


# ------------------------------------------------- the cache-less forward

def hidden_states(params, input_ids, cfg):
    """(B, S) ids -> the chosen, normed hidden states ``h_e`` (B, S, H):
    the whole sequence at once, every pass against its own keys under a
    causal mask; no cache."""
    from .transformer import _attention_xla
    B, S = input_ids.shape
    with scope("embed"):
        x = embed(params, input_ids, cfg)
        rope = M.position_tables(jnp.broadcast_to(jnp.arange(S), (B, S)),
                                 cfg.resolved_head_dim, cfg.rope_theta)
    exits = None
    for t in range(cfg.total_ut_steps):
        for layer in params["layers"]:
            with scope("attn_qkv"):
                q, k, v, _ = attention_qkv(mixer_input(x, layer, cfg=cfg),
                                           layer, cfg=cfg, rope=rope)
            with scope("attn_core"):
                a = _attention_xla(q, k, v,
                                   1.0 / math.sqrt(cfg.resolved_head_dim))
            with scope("attn_out"):
                h = attention_output(a, None, x, layer, cfg=cfg)
            with scope("mlp"):
                x, _ = mlp(h, layer, cfg=cfg)
        with scope("loss_head"), scope("loop_gate"):
            x, exits = pass_end(x, params, t, exits, cfg=cfg)
    return exits[0]
