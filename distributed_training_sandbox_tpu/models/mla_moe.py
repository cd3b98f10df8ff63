"""The latent-attention + held-experts block (the DeepSeek-V3 family's
layer as openPangu-Ultra-MoE publishes it), once, for the serving engine's
paged layer body and for the cache-less ``transformer.forward``.

``x`` is ``(B, S, H)``; every norm is an RMSNorm; ``n`` heads.

Attention (multi-head latent attention)::

    r      = norm(x; ln1)
    c_q    = norm(r . w_dq; q_norm)                          (q_lora_rank)
    q      = c_q . w_uq  ->  n x (q_nope | q_rope)           (nope | rope)
    c_kv | k_r = r . w_dkv                                   (kv_lora_rank | rope)
    c_kv   = norm(c_kv; kv_norm)
    k_rope = RoPE(k_r)    one vector, shared by all heads;  q_rope = RoPE(q_rope)
    k_nope_h = c_kv . w_uk[h]^T,   v_h = c_kv . w_uv[h]
    score_h(i, j) = (q_nope_h(i).k_nope_h(j) + q_rope_h(i).k_rope(j)) / sqrt(nope + rope)
    o_h = sum_j softmax_j(score_h(i, .)) v_h(j);   a = concat_h(o_h) . wo
    x <- x + norm(a; post_attn_norm)                         (sandwich)

What is cached per token and layer is the ROW ``[c_kv | k_rope]``
(``row_width``: 576 of bf16 at the published sizes), never K or V.  The
absorbed form serves decode against those rows without up-projecting them::

    q~_h = q_nope_h . w_uk[h]                                (kv_lora_rank)
    score_h = (q~_h . c_kv(j) + q_rope_h . k_rope(j)) / sqrt(nope + rope)
    o~_h = sum_j p c_kv(j);   o_h = o~_h . w_uv[h]

MLP: ``r2 = norm(x; ln2)``; a leading dense layer is SwiGLU of
``intermediate_size``; an expert layer is::

    s = sigmoid(r2 . w_router)      float32, all ``router_width`` experts
    T = top-k(s);  w_e = routed_scaling_factor . s_e / (sum_T s + 1e-20)
    m = SwiGLU_shared(r2) + sum_{e in T, e held here} w_e . SwiGLU_e(r2)

The expert layer (:func:`route`, :func:`moe_counts`, :func:`expert_mlp`)
also serves ``models/gdn_moe.py``, whose router scores with a softmax over
all ``router_width`` experts (a block module says which in its
``ROUTER_SCORING``) and whose shared expert is gated a token,
``sigmoid(r2 . ws_sigmoid) * SwiGLU_shared(r2)`` (an expert layer that
holds the leaf ``ws_sigmoid`` (H, 1)); it counts its
held experts as ``num_experts``, this block as ``n_routed_experts``
(``cfg.held_experts`` reads either).  It serves ``models/swa_moe.py``
too, whose router chooses by ``s + router_bias`` (an expert layer that
holds the leaf ``router_bias`` (router width,)) and weighs by ``s``, and
whose dense layers and sandwich residual are :func:`mlp` as it stands; and
``models/cca_moe.py``, which brings the router's LOGITS itself (an MLP:
``router_logits``), chooses one expert, weighs it by its softmax
probability as it is (``NORM_TOPK_PROB = False``) and has no shared expert
(an expert layer without the leaf ``ws_gate``).

``w_e`` is normalised over all of ``T`` whether or not its experts are held
here; what absent experts would add is left out (one rank's part under
expert parallelism; on one chip the layer runs without its exchange).  No
token is dropped and no expert has a capacity: the routed sum is a grouped
product over the (row, held expert) pairs the routing produced, sorted by
expert (``ops/grouped_experts.py``), so a row is multiplied by the held
experts it chose and an expert no row chose is not read; its list of
visits has room for every pair a routing can produce.  ``g``, ``u`` and ``silu(g) . u``
are in the rows' dtype, the down product, its weighting and the sum over
a row's experts in float32.  ``x <- x + norm(m; post_mlp_norm)``.

Parameter tree: ``embed`` (V, H), ``lm_head`` (H, V), ``final_norm`` (H,)
and ``layers``, a tuple of one dict a layer, ``first_k_dense_replace``
dense ones and then the expert ones: the first pattern of this repo that
is not homogeneous, so nothing is stacked.  (Stacked, XLA copied a 75 MB
slice out of the stack for every expert layer in every decode step, to
prefetch it: 0.8 ms of 14 on the v5e, PERF.md PR 26.)  Every layer holds
``ln1``, ``w_dq``, ``q_norm``, ``w_uq`` (stored out x in, (n . (nope +
rope), q rank): the absorption wants the queries head-major, and with the
heads leading XLA takes this weight as it lies instead of transposing it
in every decode step), ``w_dkv``, ``kv_norm``, ``w_uk`` (n, nope, rank),
``w_uv`` (n, rank, v), ``wo``, ``post_attn_norm``, ``ln2``,
``post_mlp_norm``; a dense layer adds ``w_gate``/``w_up``/``w_down``; an
expert layer ``w_router`` (H, router width), the held experts'
``we_gate``/``we_up`` (E, H, F) and ``we_down`` (E, F, H), and the shared
expert's ``ws_gate``/``ws_up``/``ws_down``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.grouped_experts import routed_sum
from ..utils.profiling import scope
from .gdn_hybrid import embed, final_norm, mixer_input  # noqa: F401

#: what the MLP of an expert layer counts on the device for the engine's
#: ``stats``, in this order (``moe_counts``); the serving loop sums them over
#: the layers from zeros (``COUNTS_FROM_ZERO``: a leading layer has none),
#: and they are all this block counts
COUNTERS = ("moe_assignments", "moe_assignments_held",
            "moe_experts_touched", "moe_expert_layer_steps")
DEVICE_COUNTERS = COUNTERS
COUNTS_FROM_ZERO = True

#: kinds of attention layer that are handed no rotary tables: none
NOPE_KINDS = ()


#: how this block's router scores an expert: each its own sigmoid
#: (:func:`route` reads ``cfg.block_module.ROUTER_SCORING``)
ROUTER_SCORING = "sigmoid"


def refuse(cfg, what: str):
    raise NotImplementedError(
        f"the latent-attention + held-experts block (kv_lora_rank="
        f"{cfg.kv_lora_rank}) is served by serving/engine.py and run "
        f"cache-less by models/transformer.forward only; {what} is not "
        f"built for it (ROADMAP: mechanisms the system cannot run yet)")


def check_config(cfg) -> None:
    """Called from ``TransformerConfig.__post_init__`` when the block is
    selected: the block is what the module docstring writes down, and a
    field that asks for another variant is refused by name."""
    need = ("q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "moe_intermediate_size", "router_width",
            "n_routed_experts", "n_shared_experts", "num_experts_per_tok")
    missing = [k for k in need if getattr(cfg, k) <= 0]
    if missing:
        raise ValueError(f"kv_lora_rank={cfg.kv_lora_rank} selects the "
                         f"latent block, which also needs {missing} > 0")
    if not 0 <= cfg.first_k_dense_replace <= cfg.num_hidden_layers:
        raise ValueError("first_k_dense_replace must lie in "
                         "[0, num_hidden_layers]")
    check_held_experts(cfg)
    if cfg.qk_rope_head_dim % 2:
        raise ValueError("qk_rope_head_dim must be even (rotary pairs)")
    for key, want in (("sandwich_norm", True), ("norm_topk_prob", True),
                      ("nope_interval", 0), ("tie_word_embeddings", False),
                      ("n_experts", 0), ("num_experts", 0),
                      ("attention_impl", "xla")):
        if getattr(cfg, key) != want:
            raise ValueError(f"the latent block is built with {key}="
                             f"{want!r} only, got {getattr(cfg, key)!r}")


def check_held_experts(cfg) -> None:
    """What :func:`route` needs of a config, whichever block opens the
    expert layer: the held experts lie among the router's, which has at
    least as many as a token chooses."""
    if cfg.expert_offset < 0 or \
            cfg.expert_offset + cfg.held_experts > cfg.router_width:
        raise ValueError(
            f"held experts {cfg.expert_offset}.."
            f"{cfg.expert_offset + cfg.held_experts - 1} are not among "
            f"the router's {cfg.router_width}")
    if cfg.num_experts_per_tok > cfg.router_width:
        raise ValueError("num_experts_per_tok exceeds router_width")


def row_width(cfg) -> int:
    """Elements of the one row a token caches per layer: ``[c_kv | k_rope]``."""
    return cfg.kv_lora_rank + cfg.qk_rope_head_dim


def layer_kinds(cfg) -> tuple[str, ...]:
    """Every layer caches one latent row a token, in whole-context pages."""
    return ("latent",) * cfg.num_hidden_layers


def is_expert_layer(li: int, cfg) -> bool:
    return li >= cfg.first_k_dense_replace


def _attention_weights(cfg) -> int:
    h, n = cfg.hidden_size, cfg.num_attention_heads
    return (h * cfg.q_lora_rank
            + cfg.q_lora_rank * n * (cfg.qk_nope_head_dim
                                     + cfg.qk_rope_head_dim)
            + h * row_width(cfg)
            + cfg.kv_lora_rank * n * (cfg.qk_nope_head_dim + cfg.v_head_dim)
            + n * cfg.v_head_dim * h)


def param_count(cfg) -> int:
    h = cfg.hidden_size
    n_dense = cfg.first_k_dense_replace
    n_expert = cfg.num_hidden_layers - n_dense
    norms = 4 * h + cfg.q_lora_rank + cfg.kv_lora_rank
    attn = _attention_weights(cfg) + norms
    dense = attn + 3 * h * cfg.intermediate_size
    expert = attn + h * cfg.router_width + 3 * h * cfg.moe_intermediate_size \
        * (cfg.n_routed_experts + cfg.n_shared_experts)
    return n_dense * dense + n_expert * expert \
        + 2 * cfg.vocab_size * h + h


# ------------------------------------------------------------------- init

def init_params(key: jax.Array, cfg) -> dict:
    """``transformer.init_params`` for this block: truncated normal 0.02,
    the projections back into the residual stream scaled by
    1/sqrt(2 . layers), norms at one."""
    h, n = cfg.hidden_size, cfg.num_attention_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    out_std = 0.02 / math.sqrt(2 * cfg.num_hidden_layers)
    F, Fe = cfg.intermediate_size, cfg.moe_intermediate_size
    E, Fs = cfg.n_routed_experts, cfg.n_shared_experts * Fe
    keys = iter(jax.random.split(key, 2 + 13 * cfg.num_hidden_layers))

    def tn(shape, std=0.02):
        return (std * jax.random.truncated_normal(
            next(keys), -2, 2, shape, jnp.float32)).astype(cfg.dtype)

    ones = lambda *shape: jnp.ones(shape, cfg.dtype)  # noqa: E731

    def layer(li):
        out = {
            "ln1": ones(h), "w_dq": tn((h, rq)), "q_norm": ones(rq),
            "w_uq": tn((n * (dn + dr), rq)),
            "w_dkv": tn((h, rkv + dr)), "kv_norm": ones(rkv),
            "w_uk": tn((n, dn, rkv)), "w_uv": tn((n, rkv, dv)),
            "wo": tn((n * dv, h), out_std),
            "post_attn_norm": ones(h), "ln2": ones(h),
            "post_mlp_norm": ones(h)}
        if not is_expert_layer(li, cfg):
            return {**out, "w_gate": tn((h, F)), "w_up": tn((h, F)),
                    "w_down": tn((F, h), out_std)}
        return {**out, "w_router": tn((h, cfg.router_width)),
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


# -------------------------------------------------------------- attention

def position_tables(positions, dim: int, theta: float):
    """Per-BATCH rope tables: ``positions`` (B, S) int32 -> cos/sin
    (B, S, dim / 2) float32.  Same inv_freq/angle formula as
    ``transformer._rope_tables``, so a position's table row is bitwise
    the one the one-shot path computes for it."""
    inv_freq = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32)
                               / dim)
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.cos(ang), jnp.sin(ang)


def rope_tables(positions, cfg):
    """The tables of the rotary dims, which queries and the shared key
    have apart from the others."""
    return position_tables(positions, cfg.qk_rope_head_dim, cfg.rope_theta)


def attention_scale(cfg) -> float:
    """What the scores are multiplied by: ``1/sqrt(nope + rope)``."""
    return 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)


def _rope(x, cos, sin):
    """Split-half rotation of the last axis of x (B, S, [n,] d);
    ``cos``/``sin`` ((B,) S, d/2), the tables of ``transformer.
    _rope_tables`` or of the engine's per-batch ones, broadcast over a head
    axis where x has one (the shared rotary key has none)."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    x1, x2 = jnp.split(x, 2, axis=-1)
    if x.ndim == 4:
        cos, sin = cos[..., None, :], sin[..., None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(dt)


def latent_qkv(x, layer, *, cfg, cos, sin):
    """The residual stream to the queries and the new cache rows:
    ``q_nope`` (B, S, n, nope), ``q_rope`` (B, S, n, rope) rotated, and
    ``rows`` (B, S, rank + rope) = ``[c_kv | k_rope]`` as cached."""
    from .transformer import _dense, rms_norm
    B, S, _ = x.shape
    n, dn, dr = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                 cfg.qk_rope_head_dim)
    dense, eps = _dense(cfg), cfg.rms_norm_eps
    r = rms_norm(x, layer["ln1"], eps)
    c_q = rms_norm(dense(r, layer["w_dq"]), layer["q_norm"], eps)
    q = dense(c_q, layer["w_uq"].T).reshape(B, S, n, dn + dr)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], cos, sin)
    kv = dense(r, layer["w_dkv"])
    c_kv = rms_norm(kv[..., :cfg.kv_lora_rank], layer["kv_norm"], eps)
    k_rope = _rope(kv[..., cfg.kv_lora_rank:], cos, sin)
    return q_nope, q_rope, jnp.concatenate([c_kv, k_rope], axis=-1)


def absorb_queries(q_nope, q_rope, layer):
    """The absorbed queries ``[q~ | q_rope]`` (B, S, n, rank + rope) that
    score directly against cache rows: ``q~_h = q_nope_h . w_uk[h]``."""
    qa = jnp.einsum("bsnd,ndc->bsnc", q_nope, layer["w_uk"])
    return jnp.concatenate([qa.astype(q_rope.dtype), q_rope], axis=-1)


def unabsorb_values(o_lat, layer, dtype):
    """``o~`` (B, S, n, rank), the probabilities' sum of latents, to the
    heads' outputs (B, S, n, v): ``o_h = o~_h . w_uv[h]``."""
    return jnp.einsum("bsnc,nce->bsne", o_lat.astype(dtype), layer["w_uv"])


def _scores_and_values(q_nope, q_rope, rows, layer, cfg):
    """Materialised form against the cache rows (B, K, >= rank + rope):
    up-project them to per-head keys and values, return float32 scores
    (B, n, S, K), scaled, and the values (B, K, n, v)."""
    rank = cfg.kv_lora_rank     # a pool's rows may end in zero padding
    c_kv = rows[..., :rank]
    k_rope = rows[..., rank:rank + cfg.qk_rope_head_dim]
    k_nope = jnp.einsum("bkc,ndc->bknd", c_kv, layer["w_uk"])
    v = jnp.einsum("bkc,nce->bkne", c_kv, layer["w_uv"])
    s = jnp.einsum("bsnd,bknd->bnsk", q_nope, k_nope,
                   preferred_element_type=jnp.float32) \
        + jnp.einsum("bsnr,bkr->bnsk", q_rope, k_rope,
                     preferred_element_type=jnp.float32)
    return s / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim), v


#: cache pages whose rows one step of ``attend_paged`` up-projects: 32
#: pages of 16 are 512 keys, 67 MB of float32 scores for a 256-row chunk
PAGES_PER_STEP = 32


def attend_paged(q_nope, q_rope, pool, pages, apos, layer, cfg):
    """Materialised attention of the rows at ``apos`` (B, S) against their
    slots' cached rows, read through the page table ``pages`` (B, P) from
    ``pool`` (n_pages, page, >= rank + rope): keys and values are up-projected
    ``PAGES_PER_STEP`` pages at a time under an online softmax, in a loop
    that ends at the last position any row can see, so a chunk early in a
    prompt pays for its context and not for the view's capacity.  Position
    ``p`` of a slot lives at ``(pages[p // page], p % page)``; a row sees
    positions ``<= apos``.  Returns (B, S, n, v) float32."""
    B, S, n, _ = q_nope.shape
    page = pool.shape[1]
    per = min(PAGES_PER_STEP, pages.shape[1])
    pad = -pages.shape[1] % per
    if pad:     # the null page: its positions lie past every ``apos``
        pages = jnp.pad(pages, ((0, 0), (0, pad)))
    span = per * page
    n_steps = jnp.minimum(jnp.max(apos) // span + 1, pages.shape[1] // per)

    def step(i, carry):
        m, l, acc = carry
        pg = lax.dynamic_slice_in_dim(pages, i * per, per, axis=1)
        rows = pool[pg].reshape(B, span, pool.shape[-1])
        s, v = _scores_and_values(q_nope, q_rope, rows, layer, cfg)
        pos = i * span + jnp.arange(span)
        vis = pos[None, None, :] <= apos[:, :, None]             # (B, S, K)
        s = jnp.where(vis[:, None], s, -1e30)
        # position 0 is visible to every row, so after step 0 ``m`` is a
        # real score and a masked column's exp underflows to exactly 0
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bnsk,bkne->bnse", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    init = (jnp.full((B, n, S), -jnp.inf, jnp.float32),
            jnp.zeros((B, n, S), jnp.float32),
            jnp.zeros((B, n, S, cfg.v_head_dim), jnp.float32))
    _, l, acc = lax.fori_loop(0, n_steps, step, init)
    return (acc / l[..., None]).transpose(0, 2, 1, 3)


def attention_output(o, gate, x, layer, *, cfg):
    """Heads' outputs (B, S, n, v) through ``wo`` and the post-attention
    sandwich norm, added to the residual stream (``gate``: the heads'
    output gate, which this block has not)."""
    from .transformer import _dense, rms_norm
    B, S = o.shape[:2]
    a = _dense(cfg)(o.astype(x.dtype).reshape(B, S, -1), layer["wo"])
    return x + rms_norm(a, layer["post_attn_norm"], cfg.rms_norm_eps)


# -------------------------------------------------------------------- MLP

def route(r2, w_router, cfg, *, bias=None, logits=None):
    """``r2`` (T, H) -> the routing weights of the HELD experts
    (T, held experts) float32, zero where a held expert was not among
    the row's ``num_experts_per_tok``, and the chosen ids (T, k).  The
    router's logits are ``r2 w_router`` in float32, or ``logits`` (T,
    router width) where the block makes its own (``expert_mlp``).  An
    expert's score is the block's ``ROUTER_SCORING``: its own
    ``"sigmoid"``, or a ``"softmax"`` over the router's whole width; the
    chosen scores are renormalised to sum to ``routed_scaling_factor``,
    unless the block declares ``NORM_TOPK_PROB = False``: a chosen score
    times ``routed_scaling_factor`` is then the weight as it is (a top-1
    weight renormalised would be 1 whatever the router says).  A selection
    ``bias`` (router width,) float32 is added to the scores to CHOOSE the
    experts and is no part of their weights."""
    blk = cfg.block_module
    if logits is None:
        with jax.default_matmul_precision("highest"):
            logits = r2.astype(jnp.float32) @ w_router.astype(jnp.float32)
    s = jax.nn.sigmoid(logits) if blk.ROUTER_SCORING == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    if bias is None:
        top, idx = lax.top_k(s, cfg.num_experts_per_tok)
    else:
        _, idx = lax.top_k(s + bias.astype(jnp.float32),
                           cfg.num_experts_per_tok)
        top = jnp.take_along_axis(s, idx, axis=-1)
    w = cfg.routed_scaling_factor * top
    if getattr(blk, "NORM_TOPK_PROB", True):
        w = w / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    held = cfg.expert_offset + jnp.arange(cfg.held_experts)
    hit = idx[:, :, None] == held[None, None, :]                # (T, k, E)
    return jnp.sum(jnp.where(hit, w[:, :, None], 0.0), axis=1), idx


def moe_counts(w_held, idx, valid, cfg):
    """int32 (4,) in ``COUNTERS``' order for the rows ``valid`` (T,) marks:
    (row, chosen expert) pairs over all of the router's experts, those
    whose expert is held here, held experts with at least one row, and 1
    for this expert layer's step."""
    hit = jnp.logical_and(w_held > 0, valid[:, None])
    return jnp.stack([
        jnp.sum(valid) * idx.shape[1], jnp.sum(hit),
        jnp.sum(jnp.any(hit, axis=0)), jnp.ones((), jnp.int32),
    ]).astype(jnp.int32)


def _swiglu(r, gate, up, down, dense):
    return dense(jax.nn.silu(dense(r, gate)) * dense(r, up), down)


def expert_mlp(r2, layer, *, cfg, valid=None):
    """An expert layer's MLP on the normed rows ``r2`` (B, S, H): this
    program's held experts' part of the routed sum plus, where the layer
    holds one (``ws_gate``), the shared expert (gated a token where the
    layer holds ``ws_sigmoid``).  The router is the layer's ``w_router``,
    or the block's ``router_logits(rows, layer)`` where it brings one.
    Returns the MLP's output (before any post-MLP norm) and ``moe_counts``
    of the rows ``valid`` (B, S) marks (all rows when None)."""
    from .transformer import _dense
    B, S, H = r2.shape
    rows = r2.reshape(B * S, H)
    with scope("moe_route"):
        # the selection bias and the block's own logits, where there are
        # any (keywords only then: what replaces ``route`` in a test takes
        # three arguments)
        kw = {"bias": layer["router_bias"]} if "router_bias" in layer \
            else {}
        if hasattr(cfg.block_module, "router_logits"):
            kw["logits"] = cfg.block_module.router_logits(rows, layer)
        w_held, idx = route(rows, layer.get("w_router"), cfg, **kw)
        ok = jnp.ones((B * S,), jnp.bool_) if valid is None \
            else valid.reshape(-1)
        counts = moe_counts(w_held, idx, ok, cfg)
    with scope("moe_experts"):
        routed = routed_sum(
            rows, w_held, layer["we_gate"], layer["we_up"],
            layer["we_down"], valid=ok,
            per_row=min(cfg.num_experts_per_tok, cfg.held_experts))
    if "ws_gate" not in layer:
        return routed.astype(r2.dtype).reshape(B, S, H), counts
    with scope("moe_shared"):
        shared = _swiglu(r2, layer["ws_gate"], layer["ws_up"],
                         layer["ws_down"], _dense(cfg))
        if "ws_sigmoid" in layer:
            shared = (jax.nn.sigmoid(_dense(cfg)(
                r2, layer["ws_sigmoid"]).astype(jnp.float32))
                * shared).astype(r2.dtype)
    return shared + routed.astype(r2.dtype).reshape(B, S, H), counts


def mlp(x, layer, *, cfg, valid=None):
    """Pre-MLP norm, the layer's MLP, post-MLP sandwich norm, residual.
    Returns the new ``x`` and, for an expert layer (one that holds a
    router), its ``moe_counts``; else None."""
    from .transformer import _dense, rms_norm
    r2 = rms_norm(x, layer["ln2"], cfg.rms_norm_eps)
    if "w_router" in layer:
        m, counts = expert_mlp(r2, layer, cfg=cfg, valid=valid)
    else:
        m, counts = _swiglu(r2, layer["w_gate"], layer["w_up"],
                            layer["w_down"], _dense(cfg)), None
    return x + rms_norm(m, layer["post_mlp_norm"], cfg.rms_norm_eps), counts


# ------------------------------------------------- the cache-less forward

def hidden_states(params, input_ids, cfg):
    """(B, S) ids -> final-norm hidden states (B, S, H): the whole
    sequence at once, materialised attention, no cache."""
    from .transformer import _rope_tables
    S = input_ids.shape[1]
    with scope("embed"):
        x = embed(params, input_ids, cfg)
        cos, sin = _rope_tables(S, cfg.qk_rope_head_dim, cfg.rope_theta)
    causal = jnp.tril(jnp.ones((S, S), jnp.bool_))

    for layer in params["layers"]:
        with scope("attn_qkv"):
            q_nope, q_rope, rows = latent_qkv(x, layer, cfg=cfg, cos=cos,
                                              sin=sin)
        with scope("attn_core"):
            s, v = _scores_and_values(q_nope, q_rope, rows, layer, cfg)
            p = jax.nn.softmax(jnp.where(causal, s, -1e30), axis=-1)
            o = jnp.einsum("bnsk,bkne->bsne", p.astype(v.dtype), v,
                           preferred_element_type=jnp.float32)
        with scope("attn_out"):
            x = attention_output(o, None, x, layer, cfg=cfg)
        with scope("mlp"):
            x, _ = mlp(x, layer, cfg=cfg)
    with scope("loss_head"):
        return final_norm(x, params, cfg)
