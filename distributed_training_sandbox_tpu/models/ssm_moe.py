"""Mamba-2 state-space layers beside GQA attention layers with no position
embedding, over routed and shared experts, with the family's four
multipliers (the layer granite-4.0-h-small publishes, ``model_type``
``granitemoehybrid``), for the serving engine's paged layer body and for
the cache-less ``transformer.forward``.

It holds what DIFFERS from the blocks that are there and copies none of
them: the expert layer (routing over the router's whole width, the held
experts' part of the routed sum as PR 39's grouped product, the shared
expert, the counters) is ``models/mla_moe.py``'s ``expert_mlp`` with a
softmax router; the depthwise causal conv and its tail are
``models/gdn_hybrid.py``'s ``causal_conv``; the layer loop is the engine's
``_paged_block_forward`` and ``gdn_hybrid.hidden_states``, which reach
this module as ``cfg.block_module`` and ``cfg.linear_mixer`` (the engine
loop's docstring: what a block and what a linear mixer bring).  Written
here: the Mamba-2 mixer (projections, the conv's bias, softplus ``dt``, the
recurrence's two forms, ``D``, the gated norm), attention at the scale
``attention_multiplier`` with no rotary embedding, the multipliers, the
tied scaled head, and the layer kinds from ``layer_types``.

``x`` is the residual stream ``(B, S, H)``; every norm a plain RMSNorm
(``x / rms(x) * w``, ``w`` initialised 1) of ``rms_norm_eps``; no bias but
the conv's.  ``x_0 = embed[ids] * embedding_multiplier``.  Layer ``i`` is an
attention layer where ``layer_types[i] == "attention"``, else a Mamba-2
layer::

    h = x + residual_multiplier * Mixer_i(norm(x; input_norm))
    y = h + residual_multiplier * (MoE(r2) + Shared(r2)),  r2 = norm(h; post_attn_norm)
    logits = norm(x_L; final_norm) embed^T / logits_scaling            tied

Attention mixer (``n`` heads of ``hd``, ``n_kv`` KV heads; NO rotary
embedding, ``rope_theta`` unused)::

    a_j = causal softmax(attention_multiplier * q_j k_m(j)^T) v_m(j),  m(j) = j // (n / n_kv)
    Mixer(r) = [a_j]_j wo

What one token caches in such a layer is its K and V rows ``(n_kv, hd)``,
unrotated, in pages.

Mamba-2 mixer (``d_inner = mamba_expand * H = mamba_n_heads * mamba_d_head``,
ONE B/C group (``mamba_n_groups`` 1) of ``ds = mamba_d_state``, a conv of
``K = mamba_d_conv`` over ``C = d_inner + 2 ds`` channels)::

    [z | u | dt] = r [w_z | w_xbc | w_dt]      widths d_inner | C | heads; no bias
    c_t = silu(sum_{k<K} conv_w[k] * u_{t-K+1+k} + conv_b)     depthwise, causal, WITH bias
    [xs | B | C] = c_t                         xs as (heads, head dim); B, C shared by every head
    D_t[h] = softplus(dt_t[h] + dt_bias[h]);   a_t[h] = exp(D_t[h] * A[h]),  A = -exp(A_log)
    S_t[h] = a_t[h] S_{t-1}[h] + (D_t[h] xs_t[h]) (x) B_t       S[h] is (head dim, ds), float32
    o_t[h] = S_t[h] C_t + Dskip[h] * xs_t[h]
    Mixer(r)_t = norm(o_t * silu(z_t); gate_norm) w_out        the norm over ALL of d_inner

The published in-projection is one matrix ``H x (d_inner + C + heads)``; it
is held here as its three column blocks (leaves ``w_z``, ``w_xbc``,
``w_dt``), so that the gate is read where the output is made.

What one REQUEST keeps in such a layer does not grow with its length: the
state ``S`` (heads, head dim, ds) in float32 and the conv's tail, the last
``K - 1`` rows of ``u``.  A state AT REST, in the engine's slots, is stored
``(ds, heads * head dim)`` (:func:`slot_shape`): the state dim down the
sublanes, every head's dims side by side on the lanes, whole (8, 128) tiles
at the published widths (128 x 8,192) with no padding, so that B and C are
columns that broadcast along the lanes and a head's decay a row that
broadcasts down the sublanes.  Two forms of the recurrence live here and
must agree: :func:`recurrent_step` (one token for every slot: decode; on a
TPU the Pallas kernel of ``ops/ssm_step.py``) and :func:`chunked_scan`, the
chunked (SSD) form of a prefill chunk that STARTS from a carried state and
ENDS in one, at blocks of ``SCAN_BLOCK`` = ``mamba_chunk_size`` rows.  With
``G_i = prod_{t<=i} a_t`` inside a block and ``X = D xs``::

    O = ((C B^T) * L) X + (C * G) S_0 + Dskip xs,    L_ij = G_i / G_j  (j <= i)
    S_end = G_end S_0 + (B * (G_end / G))^T X

No triangular system: ``C B^T`` is one product a block for all heads.  The
block size is arithmetic, not a width: the result does not depend on it.

MoE (router width ``router_width``, ``num_experts_per_tok`` chosen, this
program HOLDS ``num_local_experts`` of them from ``expert_offset``; expert
width ``intermediate_size``, shared width ``shared_intermediate_size``,
ungated)::

    T = top-k(r2 w_router);  w = softmax over the CHOSEN logits, float32
    MoE = sum_{e in T, e held} w_e SwiGLU_e(r2);   Shared = SwiGLU_s(r2)

``mla_moe.route`` takes the softmax over the whole width and renormalises
over the chosen, which is the same weights.  What the absent experts would
add is left out (one rank's part under expert parallelism).

Parameter tree: ``embed`` (V, H) (also the head: tied), ``final_norm`` (H,)
and ``layers``, a tuple of one dict a layer (two kinds, nothing stacked).
Every layer holds ``input_norm``, ``post_attn_norm`` (H,), ``w_router``
(H, router width), the held experts' ``we_gate``/``we_up`` (E, H, F) and
``we_down`` (E, F, H), the shared expert's ``ws_gate``/``ws_up`` (H, Fs)
and ``ws_down`` (Fs, H); an attention layer adds ``wq`` (H, n hd), ``wk``,
``wv`` (H, n_kv hd), ``wo`` (n hd, H); a Mamba-2 layer ``w_z`` (H,
d_inner), ``w_xbc`` (H, C), ``w_dt`` (H, heads), ``conv_w`` (K, C),
``conv_b`` (C,), ``A_log``, ``dt_bias``, ``Dskip`` (heads,), ``gate_norm``
(d_inner,), ``w_out`` (d_inner, H).
"""

from __future__ import annotations

import contextlib
import math

import jax
import jax.numpy as jnp
from jax import lax

from ..utils.profiling import scope
from . import mla_moe as M
from .gdn_hybrid import COUNTERS as _STATE_COUNTERS
from .gdn_hybrid import (COUNTS_FROM_ZERO, NOPE_KINDS,  # noqa: F401
                         causal_conv, hidden_states)

#: the published ``mamba_chunk_size``, which ``check_config`` holds a config
#: to, and the rows of one block of the chunked scan (arithmetic, not a
#: width: the tests scan at 4 and 16)
MAMBA_CHUNK_SIZE = 256
SCAN_BLOCK = MAMBA_CHUNK_SIZE

#: what the engine counts for this block in ``stats``: the expert layers'
#: four and the live states, summed on the device through a burst (the
#: first five); slots reset at a grant and rows scanned, on the host
COUNTERS = M.COUNTERS + _STATE_COUNTERS
DEVICE_COUNTERS = COUNTERS[:5]

#: how this block's router scores an expert (``mla_moe.route``)
ROUTER_SCORING = "softmax"

#: the scope the engine opens round this block's paged attention beneath
#: ``attn_core`` (``profiling.ATTENTION_SUBSCOPES``)
PAGED_ATTENTION_SCOPE = "attn_paged"


def refuse(cfg, what: str):
    raise NotImplementedError(
        f"the Mamba-2 + attention block with held experts (mamba_d_state="
        f"{cfg.mamba_d_state}, {layer_kinds(cfg).count('full')} attention "
        f"layers of {cfg.num_hidden_layers}, num_local_experts="
        f"{cfg.num_local_experts} of {cfg.router_width} held) is served by "
        f"serving/engine.py and run cache-less by models/transformer."
        f"forward only; {what} is not built for it (ROADMAP: mechanisms "
        f"the system cannot run yet)")


def check_config(cfg) -> None:
    """Called from ``TransformerConfig.__post_init__`` when the block is
    selected: the block is what the module docstring writes down, and a
    field that asks for another variant is refused by name."""
    need = ("mamba_n_heads", "mamba_d_head", "mamba_d_conv",
            "num_local_experts", "router_width", "num_experts_per_tok",
            "shared_intermediate_size")
    missing = [k for k in need if getattr(cfg, k) <= 0]
    if missing:
        raise ValueError(f"mamba_d_state={cfg.mamba_d_state} selects the "
                         f"Mamba-2 + attention block, which also needs "
                         f"{missing} > 0")
    if len(cfg.layer_types) < cfg.num_hidden_layers or \
            set(cfg.layer_types) - {"mamba", "attention"}:
        raise ValueError(
            f"layer_types must name each of the {cfg.num_hidden_layers} "
            f"layers 'mamba' or 'attention' (a longer list is the "
            f"published one, whose first num_hidden_layers are run), got "
            f"{cfg.layer_types!r}")
    if inner_width(cfg) != cfg.mamba_expand * cfg.hidden_size:
        raise ValueError(
            f"mamba_n_heads x mamba_d_head = {inner_width(cfg)} is not "
            f"mamba_expand x hidden_size = "
            f"{cfg.mamba_expand * cfg.hidden_size}")
    if cfg.mamba_d_conv < 2:
        raise ValueError("mamba_d_conv must be >= 2 (a conv of width 1 "
                         "carries no tail)")
    M.check_held_experts(cfg)
    for key, want in (("mamba_n_groups", 1),
                      ("mamba_chunk_size", MAMBA_CHUNK_SIZE),
                      ("tie_word_embeddings", True), ("nope_interval", 0),
                      ("n_experts", 0), ("n_routed_experts", 0),
                      ("num_experts", 0), ("kv_lora_rank", 0),
                      ("linear_key_head_dim", 0), ("sliding_window", 0),
                      ("routed_scaling_factor", 1.0),
                      ("attention_impl", "xla")):
        if getattr(cfg, key) != want:
            raise ValueError(f"the Mamba-2 + attention block is built with "
                             f"{key}={want!r} only, got "
                             f"{getattr(cfg, key)!r}")


# --------------------------------------------- what the linear mixer brings

def layer_kinds(cfg) -> tuple[str, ...]:
    """One entry a layer: ``"full"`` (K/V rows in whole-context pages)
    where ``layer_types`` says "attention", else ``"linear"`` (a Mamba-2
    layer: a state slot and a conv tail, no pages)."""
    return tuple("full" if t == "attention" else "linear"
                 for t in cfg.layer_types[:cfg.num_hidden_layers])


def inner_width(cfg) -> int:
    return cfg.mamba_n_heads * cfg.mamba_d_head


def conv_channels(cfg) -> int:
    return inner_width(cfg) + 2 * cfg.mamba_n_groups * cfg.mamba_d_state


def state_shape(cfg) -> tuple[int, int, int]:
    """One slot's recurrent state in one Mamba-2 layer (float32): a
    (head dim, state dim) matrix a head."""
    return (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state)


def slot_shape(cfg) -> tuple[int, int]:
    """One slot's state in one Mamba-2 layer AS STORED: ``(ds, heads *
    head dim)`` float32 (module docstring)."""
    n, hd, ds = state_shape(cfg)
    return (ds, n * hd)


def pack_state(s):
    """What :func:`chunked_scan` carries, (B, ds, n, hd), -> the stored
    layout (B, ds, n * hd): the same bytes."""
    B, ds, n, hd = s.shape
    return s.reshape(B, ds, n * hd)


def unpack_state(s, n: int):
    """The stored layout (B, ds, n * hd) -> (B, ds, n, hd)."""
    B, ds, width = s.shape
    return s.reshape(B, ds, n, width // n)


def tail_shape(cfg) -> tuple[int, int]:
    """One slot's conv tail in one Mamba-2 layer (``cfg.dtype``)."""
    return (cfg.mamba_d_conv - 1, conv_channels(cfg))


def slot_state_bytes(cfg) -> int:
    """Bytes one batch slot holds in ONE Mamba-2 layer: state + tail."""
    return math.prod(state_shape(cfg)) * 4 \
        + math.prod(tail_shape(cfg)) * jnp.dtype(cfg.dtype).itemsize


def mamba_mixer_param_count(cfg) -> int:
    h, d, n = cfg.hidden_size, inner_width(cfg), cfg.mamba_n_heads
    C = conv_channels(cfg)
    return h * (d + C + n) + d * h + (cfg.mamba_d_conv + 1) * C + 3 * n + d


def param_count(cfg) -> int:
    h, hd = cfg.hidden_size, cfg.resolved_head_dim
    nq, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    common = 2 * h + h * cfg.router_width \
        + 3 * h * cfg.intermediate_size * cfg.num_local_experts \
        + 3 * h * cfg.shared_intermediate_size
    attn = common + h * hd * (2 * nq + 2 * nkv)
    mamba = common + mamba_mixer_param_count(cfg)
    n_attn = layer_kinds(cfg).count("full")
    return n_attn * attn + (cfg.num_hidden_layers - n_attn) * mamba \
        + cfg.vocab_size * h + h


# ------------------------------------------------------------------- init

def init_params(key: jax.Array, cfg) -> dict:
    """``transformer.init_params`` for this block: truncated normal 0.02,
    the projections back into the residual stream scaled by
    1/sqrt(2 . layers), norms at one; the conv's weights and bias uniform
    in +-1/sqrt(K); ``A = exp(A_log)`` uniform in [1, 16],
    ``softplus(dt_bias)`` log-uniform in [1e-3, 1e-1] (through the inverse
    softplus), ``Dskip`` 1: the family's convention, so a head's decay a
    token runs from about 0.2 to nearly 1.  No ``lm_head``: tied.  The
    embedding is drawn at 0.02 / ``embedding_multiplier``, so that ``x_0``
    has the scale the other blocks' has: drawn at 0.02 and then scaled, the
    token's own embedding outweighs everything ten layers add, the TIED head
    reads it back 30 sigma above every other logit, and every served token
    is the one before it (PERF.md section 6, PR 40: the first chip run's
    check read a gap of 0.0 at 1,280 of 1,280 positions)."""
    h, hd = cfg.hidden_size, cfg.resolved_head_dim
    nq, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    E, F, Fs = (cfg.num_local_experts, cfg.intermediate_size,
                cfg.shared_intermediate_size)
    d, n, C, K = (inner_width(cfg), cfg.mamba_n_heads, conv_channels(cfg),
                  cfg.mamba_d_conv)
    out_std = 0.02 / math.sqrt(2 * cfg.num_hidden_layers)
    keys = iter(jax.random.split(key, 1 + 16 * cfg.num_hidden_layers))

    def tn(shape, std=0.02):
        return (std * jax.random.truncated_normal(
            next(keys), -2, 2, shape, jnp.float32)).astype(cfg.dtype)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

    ones = lambda *shape: jnp.ones(shape, cfg.dtype)  # noqa: E731

    def layer(kind):
        out = {"input_norm": ones(h), "post_attn_norm": ones(h),
               "w_router": tn((h, cfg.router_width)),
               "we_gate": tn((E, h, F)), "we_up": tn((E, h, F)),
               "we_down": tn((E, F, h), out_std),
               "ws_gate": tn((h, Fs)), "ws_up": tn((h, Fs)),
               "ws_down": tn((Fs, h), out_std)}
        if kind == "full":
            return {**out, "wq": tn((h, nq * hd)), "wk": tn((h, nkv * hd)),
                    "wv": tn((h, nkv * hd)), "wo": tn((nq * hd, h), out_std)}
        dt = jnp.exp(uniform((n,), math.log(1e-3), math.log(1e-1)))
        return {**out, "w_z": tn((h, d)), "w_xbc": tn((h, C)),
                "w_dt": tn((h, n)),
                "conv_w": uniform((K, C), -K ** -0.5,
                                  K ** -0.5).astype(cfg.dtype),
                "conv_b": uniform((C,), -K ** -0.5,
                                  K ** -0.5).astype(cfg.dtype),
                "A_log": jnp.log(uniform((n,), 1.0, 16.0)).astype(cfg.dtype),
                # softplus^-1(dt)
                "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(cfg.dtype),
                "Dskip": ones(n), "gate_norm": ones(d),
                "w_out": tn((d, h), out_std)}

    return {
        "embed": tn((cfg.vocab_size, h), 0.02 / cfg.embedding_multiplier),
        "layers": tuple(layer(kind) for kind in layer_kinds(cfg)),
        "final_norm": ones(h),
    }


# ------------------------------------------------------ the Mamba-2 mixer

def linear_inputs(r, layer, tail, valid, *, cfg):
    """The normed rows ``r`` (B, S, H) to what the recurrence takes, all
    float32: ``xd = D xs`` (B, S, n, hd), the update's left factor; ``Bm``,
    ``Cm`` (B, S, ds); ``g = D A`` = log a (B, S, n); ``skip = Dskip xs``
    (B, S, n, hd); ``xd`` and ``g`` 0 where ``valid`` (B, S) is False, so
    such a row changes no state; and the conv's new tail."""
    from .transformer import _dense
    B, S, _ = r.shape
    n, hd, ds = state_shape(cfg)
    d = n * hd
    dense = _dense(cfg)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    with scope("lin_conv"):
        c, new_tail = causal_conv(dense(r, layer["w_xbc"]), tail,
                                  layer["conv_w"],
                                  jnp.sum(valid.astype(jnp.int32), axis=1))
        c = jax.nn.silu(c + f32(layer["conv_b"])).astype(r.dtype)
    xs = f32(c[..., :d]).reshape(B, S, n, hd)
    dt = jax.nn.softplus(f32(dense(r, layer["w_dt"])) + f32(layer["dt_bias"]))
    dt = jnp.where(valid[..., None], dt, 0.0)
    return (dt[..., None] * xs, f32(c[..., d:d + ds]), f32(c[..., d + ds:]),
            -jnp.exp(f32(layer["A_log"])) * dt,
            f32(layer["Dskip"])[:, None] * xs, new_tail)


_STEP_KERNEL = False


@contextlib.contextmanager
def step_kernel(on: bool):
    """While tracing inside this context, :func:`recurrent_step` is the
    Pallas step kernel (``ops/ssm_step.py``) when ``on``
    (``gdn_hybrid.step_kernel``'s twin: how the engine's decode program
    says which form it was built with)."""
    global _STEP_KERNEL
    old, _STEP_KERNEL = _STEP_KERNEL, bool(on)
    try:
        yield
    finally:
        _STEP_KERNEL = old


def step_kernel_engages(n: int, hd: int, ds: int) -> bool:
    """Whether a decode step over states of ``state_shape`` ``(n, hd,
    ds)`` that asks for the step kernel gets it: on a TPU for the shapes it
    compiles for, elsewhere always (interpreted)."""
    from ..ops.ssm_step import step_kernel_takes
    return jax.default_backend() != "tpu" or step_kernel_takes(n, hd, ds)


def recurrent_step(xd, Bm, Cm, g, skip, state):
    """One token of the recurrence for every slot: xd, skip (B, n, hd),
    Bm, Cm (B, ds), g (B, n), ``state`` (B, ds, n * hd) float32, the slots
    as stored (:func:`slot_shape`).  Returns ``o`` (B, n, hd) and the new
    state in the same layout.  Elementwise products and sums in float32: a
    state is read as it is stored, never rounded for an MXU pass.  ``g =
    0, xd = 0`` leaves a state bit for bit as it was.

    Two forms.  Inside :func:`step_kernel` (the engine's decode program on
    a TPU) the Pallas kernel of ``ops/ssm_step.py``: every slot with a
    non-zero ``g`` or ``xd`` read once and written once in place, the
    others not touched (their ``o`` is their ``skip``).  Otherwise this
    XLA form, the tests' reference."""
    B, n, hd = xd.shape
    if _STEP_KERNEL and step_kernel_engages(n, hd, Bm.shape[-1]):
        from ..ops.ssm_step import ssm_decode_step
        o, state = ssm_decode_step(xd, Bm, Cm, g, state)
        return o + skip, state
    a = jnp.repeat(jnp.exp(g), hd, axis=-1)[:, None]          # (B, 1, n hd)
    s = a * state + Bm[:, :, None] * xd.reshape(B, 1, n * hd)
    return jnp.sum(Cm[:, :, None] * s, axis=1).reshape(B, n, hd) + skip, s


def chunked_scan(xd, Bm, Cm, g, skip, state):
    """The recurrence over S rows from a carried ``state`` (B, ds, n, hd)
    (the stored layout with the heads apart): xd, skip (B, S, n, hd), Bm,
    Cm (B, S, ds), g (B, S, n), all float32.  Returns ``o`` (B, S, n, hd)
    and the state after the last row (module docstring: the chunked form;
    ``SCAN_BLOCK`` rows a block, S padded up to whole blocks with rows
    that change nothing).  B and C are shared by every head, so ``C B^T``
    and both products with the state are ONE matrix product a block for
    all the heads; the decay ``L`` and the product with ``X`` are a head's
    own."""
    B, S, n, hd = xd.shape
    ds = Bm.shape[-1]
    L = min(SCAN_BLOCK, S)
    pad = -S % L
    if pad:
        xd, Bm, Cm, g = (jnp.pad(a, ((0, 0), (0, pad))
                                 + ((0, 0),) * (a.ndim - 2))
                         for a in (xd, Bm, Cm, g))
    N = (S + pad) // L
    # blocks lead, so the sequential part scans them
    blocks = lambda a: jnp.moveaxis(  # noqa: E731
        a.reshape((B, N, L) + a.shape[2:]), 1, 0)
    xd, Bm, Cm = blocks(xd), blocks(Bm), blocks(Cm)
    cum = jnp.cumsum(blocks(g), axis=2)                   # log G_i (N, B, L, n)
    G = jnp.exp(cum)
    tri = jnp.tril(jnp.ones((L, L), jnp.bool_))
    # L_ij = G_i / G_j for j <= i, 0 above the diagonal (the difference is
    # <= 0 where it is kept: no overflow), a head's own: (N, B, n, L, L)
    ch = jnp.swapaxes(cum, 2, 3)
    diff = ch[..., :, None] - ch[..., None, :]
    decay = jnp.where(tri, jnp.exp(jnp.where(tri, diff, 0.0)), 0.0)
    cb = jnp.einsum("zbis,zbjs->zbij", Cm, Bm)
    o = jnp.einsum("zbhij,zbjhp->zbihp", cb[:, :, None] * decay, xd)
    # what a block leaves in the state: rows weighted by G_end / G_j
    x_end = (jnp.exp(cum[:, :, -1:] - cum)[..., None] * xd).reshape(
        N, B, L, n * hd)
    G_end = jnp.repeat(G[:, :, -1], hd, axis=-1)[:, :, None]   # (N, B, 1, n hd)

    def block(s, xs):
        Cm, Bm, x_end, G_end = xs
        o_state = Cm @ s                                  # (B, L, n hd)
        return G_end * s + jnp.swapaxes(Bm, -1, -2) @ x_end, o_state

    state, o_state = lax.scan(block, state.reshape(B, ds, n * hd),
                              (Cm, Bm, x_end, G_end))
    o = o + G[..., None] * o_state.reshape(N, B, L, n, hd)
    o = jnp.moveaxis(o, 0, 1).reshape(B, N * L, n, hd)[:, :S]
    return o + skip, state.reshape(B, ds, n, hd)


# ------------------------------------------------- what the block brings

def embed(params, ids, cfg):
    """``embed[ids] * embedding_multiplier``."""
    x = params["embed"].astype(cfg.dtype)[ids]
    return (x.astype(jnp.float32) * cfg.embedding_multiplier).astype(
        cfg.dtype)


def rope_tables(positions, cfg):
    """No position embedding: position reaches the attention layers
    through the recurrent ones."""
    return None


def attention_scale(cfg) -> float | None:
    """What the attention scores are multiplied by:
    ``attention_multiplier``, not ``1/sqrt(head_dim)`` (None: that)."""
    return float(cfg.attention_multiplier) or None


def norm(x, w, cfg):
    from .transformer import rms_norm
    return rms_norm(x, w, cfg.rms_norm_eps)


def mixer_input(x, layer, *, cfg):
    """What a mixer reads: ``norm(x; input_norm)``."""
    return norm(x, layer["input_norm"], cfg)


def add_scaled(x, y, cfg):
    """``x + residual_multiplier * y`` in float32, as the residual's
    dtype."""
    return (x.astype(jnp.float32) + cfg.residual_multiplier
            * y.astype(jnp.float32)).astype(x.dtype)


def attention_qkv(r, layer, *, cfg, rope=None):
    """``q`` (B, S, n, hd), ``k``, ``v`` (B, S, n_kv, hd) from the normed
    rows: three projections, no norm, no rotary embedding; and the heads'
    output gate, which this block has not (None)."""
    from .transformer import _dense
    B, S, _ = r.shape
    hd = cfg.resolved_head_dim
    dense = _dense(cfg)
    return (dense(r, layer["wq"]).reshape(B, S, cfg.num_attention_heads, hd),
            dense(r, layer["wk"]).reshape(B, S, cfg.num_key_value_heads, hd),
            dense(r, layer["wv"]).reshape(B, S, cfg.num_key_value_heads, hd),
            None)


def attention_output(attn, gate, x, layer, *, cfg):
    """The heads' outputs ``attn`` (B, S, ..heads.., hd) float32 through
    ``wo``, times ``residual_multiplier``, onto the residual stream."""
    from .transformer import _dense
    B, S = attn.shape[:2]
    return add_scaled(x, _dense(cfg)(
        attn.astype(x.dtype).reshape(B, S, -1), layer["wo"]), cfg)


def linear_mixer_output(o, r, x, layer, *, cfg):
    """A Mamba-2 layer's ``h`` from the recurrence's outputs ``o`` (B, S,
    n, hd) float32: gated by ``silu(z)``, normed over the whole inner
    width, through ``w_out``, times ``residual_multiplier``."""
    from .transformer import _dense
    B, S = o.shape[:2]
    dense = _dense(cfg)
    z = jax.nn.silu(dense(r, layer["w_z"]).astype(jnp.float32))
    y = norm(o.reshape(B, S, -1) * z, layer["gate_norm"], cfg)
    return add_scaled(x, dense(y.astype(x.dtype), layer["w_out"]), cfg)


def mlp(h, layer, *, cfg, valid=None):
    """``y = h + residual_multiplier * (MoE + Shared)(norm(h;
    post_attn_norm))`` and the expert layer's ``mla_moe.moe_counts`` of the
    rows ``valid`` marks."""
    m, counts = M.expert_mlp(norm(h, layer["post_attn_norm"], cfg), layer,
                             cfg=cfg, valid=valid)
    return add_scaled(h, m, cfg), counts


def final_norm(x, params, cfg):
    return norm(x, params["final_norm"], cfg)
