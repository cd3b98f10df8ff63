"""The hybrid block of gated delta-rule (linear-attention) layers and
full-attention layers (the layer Olmo-Hybrid-7B publishes), once, for the
serving engine's paged layer body and for the cache-less
``transformer.forward``.

``x`` is the residual stream ``(B, S, H)``; every norm is an RMSNorm of
``rms_norm_eps``; no bias anywhere.  Layer ``i`` (0-based) is a
full-attention layer where ``(i + 1) % full_attention_interval == 0`` and a
linear one otherwise (interval 4: three linear, then one full).

Residual path (the OLMo 2/3 family's reordered norm: no norm before a
mixer, one after it)::

    h = x + norm(Mixer(x); post_attn_norm)
    y = h + norm(MLP(h); post_mlp_norm),   MLP(h) = (silu(h w_gate) * h w_up) w_down

and a final norm before the untied head.

Full-attention mixer (``n`` heads of ``hd``, ``n_kv`` KV heads)::

    q = norm(x wq; q_norm),  k = norm(x wk; k_norm)    norms over the WHOLE projection
    v = x wv;   no rotary embedding (position reaches these layers through
                the recurrent ones)
    a = causal softmax(q k^T / sqrt(hd)) v;   Mixer(x) = a wo

What one token caches in such a layer is its K and V rows ``(n_kv, hd)``,
in pages, as the dense block does.

Linear mixer, the gated delta rule (``n_k`` key heads of ``dk``, ``n``
value heads of ``dv``, ``n`` a multiple of ``n_k``: value head ``r`` reads
the ``q`` and ``k`` of key head ``r // (n / n_k)``, GROUPED value heads;
this block's published config has ``n = n_k``; ``C = 2 n_k dk + n dv`` conv
channels)::

    u = [x w_q | x w_k | x w_v]                                   (B, S, C)
    c_t = sum_{j<K} conv_w[j] * u_{t-K+1+j}      depthwise, CAUSAL, width K
    q~, k~, v~ = silu(c) split per head
    q_t = q~_t / |q~_t| / sqrt(dk),  k_t = k~_t / |k~_t|,  v_t = v~_t
                       (|.|: sqrt(sum of squares + 1e-6) over a head's dk)
    beta_t  = b sigmoid(x_t w_b)    a value head; b = 2 with
                                    ``linear_allow_neg_eigval``, else 1
    alpha_t = exp(-exp(A_log) softplus(x_t w_a + dt_bias))   a value head, in (0, 1)
    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T     S_0 = 0
    o_t = S_t^T q_t
    Mixer(x)_t = [norm(o_t; o_norm) * silu(x_t w_g)] w_o    norm over a head's dv

The linear mixer (everything from :func:`causal_conv` to
:func:`linear_output`, the state's layouts and the mixer's leaves) also
serves ``models/gdn_moe.py``, whose block has two value heads a key head
and ``b = 1``.

What one REQUEST keeps in such a layer does not grow with its length: the
state ``S`` (n, dk, dv) in float32 and the conv's tail, the last ``K - 1``
rows of ``u``.  Three forms of the same recurrence live here and must
agree: :func:`recurrent_step` (one token: decode), :func:`chunked_scan`
(a prefill chunk that STARTS from a carried state and ENDS in one) and,
built on the second from a zero state, the whole-sequence form of
:func:`hidden_states`.

A state AT REST, in the engine's slots, is stored lane-dense
(:func:`slot_shape`, :func:`pack_state`): ``(dk, n * dv)``, the heads'
value dims side by side, so that at the published widths a slot is whole
(8, 128) tiles (96 x 5,760) where ``(n, dk, dv)`` pads every row of 192 to
256 lanes.  :func:`recurrent_step` takes and returns that layout (a decode
step touches every slot in place and converts none); the scan keeps
``(n, dk, dv)`` and the engine converts the one slot a prefill chunk
carries at the chunk's boundary.

The chunked scan, per sub-chunk of ``SCAN_CHUNK`` rows from the carried
state ``S_0`` (``g_t = log alpha_t``, ``G_i = exp(sum_{t<=i} g_t)``): with
``u_i = beta_i (v_i - alpha_i S_{i-1}^T k_i)`` the recurrence is ``S_i =
alpha_i S_{i-1} + k_i u_i^T``, so ``S_i = G_i S_0 + sum_{j<=i} (G_i/G_j)
k_j u_j^T`` and the ``u`` solve the unit lower-triangular system::

    (I + A) U = beta * V - (beta G * K) S_0,    A_ij = beta_i (G_i/G_j) k_i.k_j  (j < i)
    O = (G * Q) S_0 + ((Q K^T) * D) U,          D_ij = G_i/G_j  (j <= i)
    S_C = G_C S_0 + (K * (G_C/G))^T U

``T = (I + A)^-1`` does not depend on ``S_0``: it, ``T (beta V)`` and
``T (beta G K)`` are computed for all sub-chunks at once and only the
three products with the state run in sequence.  ``T`` is obtained by
forward substitution (:func:`unit_lower_inverse`): row ``i`` of ``T`` is
``e_i - sum_{j<i} A_ij T_j``, ``SCAN_CHUNK - 1`` row updates in sequence,
each one an elementwise float32 pass over every system of the chunk at
once (sub-chunks x heads of them, side by side on the lanes: 240 a layer
at Olmo-Hybrid's widths, 128 at Qwen3-Next's).  These are a triangular
solve's own operations in its own order, so ``T`` is exact where the solve
is: with ``beta = 2`` and a repeated key ``A`` is 2 everywhere below the
diagonal, the entries of ``T`` stay at 2 (``|1 - beta k.k| <= 1``: the
recurrence does not grow) while the POWERS of ``A`` reach 1e6 in 16 rows,
which is why no series in ``A`` and no product of partial inverses is
used (PERF.md, PR 34: XLA's ``triangular_solve`` ran these rows one system
block after another, 10.8 ms a 512-row chunk on a v5e; this loop takes
0.7).  A row past the prompt's end has ``beta = 0`` and ``g = 0``: its
``u`` is 0 and it changes no state.

Parameter tree: ``embed`` (V, H), ``lm_head`` (H, V), ``final_norm`` (H,)
and ``layers``, a tuple of one dict a layer (two kinds, nothing stacked).
Every layer holds ``post_attn_norm``, ``post_mlp_norm``, ``w_gate``,
``w_up``, ``w_down``; a full-attention layer adds ``wq``, ``wk``, ``wv``,
``wo``, ``q_norm``, ``k_norm``; a linear layer ``w_q``, ``w_k`` (H, n_k dk),
``w_v``, ``w_g`` (H, n dv), ``w_a``, ``w_b`` (H, n), ``A_log``, ``dt_bias``
(n,), ``conv_w`` (K, C), ``o_norm`` (dv,), ``w_o`` (n dv, H).
"""

from __future__ import annotations

import contextlib
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from ..utils.profiling import scope

#: rows of one sub-chunk of the chunked scan: the (rows x rows) triangular
#: system and the intra-chunk products grow with it, the sequential steps
#: shrink
SCAN_CHUNK = 64

#: what the engine counts for this block in ``stats`` (serving/engine.py):
#: live states a decode step read and wrote, summed over the steps (on the
#: device, read at a burst's sync); slots whose state a grant reset; valid
#: rows the prefill chunks scanned
COUNTERS = ("state_slot_steps", "state_resets", "lin_scan_rows")
DEVICE_COUNTERS = COUNTERS[:1]
#: expert layers' counters, where a block with this mixer has them, are
#: summed from the first layer's (every layer has them), not from zeros
COUNTS_FROM_ZERO = False

#: kinds of attention layer that are handed no rotary tables: none
NOPE_KINDS = ()

L2_EPS = 1e-6


def refuse(cfg, what: str):
    raise NotImplementedError(
        f"the gated delta-rule hybrid block (linear_key_head_dim="
        f"{cfg.linear_key_head_dim}, full attention every "
        f"{cfg.full_attention_interval} layers) is served by "
        f"serving/engine.py and run cache-less by models/transformer."
        f"forward only; {what} is not built for it (ROADMAP: mechanisms "
        f"the system cannot run yet)")


def check_linear(cfg) -> None:
    """What every block with this linear mixer needs of its config."""
    need = ("full_attention_interval", "linear_num_key_heads",
            "linear_num_value_heads", "linear_value_head_dim",
            "linear_conv_kernel_dim")
    missing = [k for k in need if getattr(cfg, k) <= 0]
    if missing:
        raise ValueError(f"linear_key_head_dim={cfg.linear_key_head_dim} "
                         f"selects the gated delta-rule hybrid block, which "
                         f"also needs {missing} > 0")
    if cfg.linear_num_value_heads % cfg.linear_num_key_heads:
        raise ValueError("the linear mixer groups whole value heads under "
                         "a key head: linear_num_value_heads must be a "
                         "multiple of linear_num_key_heads")
    if cfg.linear_conv_kernel_dim < 2:
        raise ValueError("linear_conv_kernel_dim must be >= 2 (a conv of "
                         "width 1 carries no tail)")
    for key, want in (("nope_interval", 0), ("tie_word_embeddings", False),
                      ("n_experts", 0), ("kv_lora_rank", 0),
                      ("attention_impl", "xla")):
        if getattr(cfg, key) != want:
            raise ValueError(f"the gated delta-rule hybrid block is built "
                             f"with {key}={want!r} only, got "
                             f"{getattr(cfg, key)!r}")


def check_config(cfg) -> None:
    """Called from ``TransformerConfig.__post_init__`` when the block is
    selected: the block is what the module docstring writes down, and a
    field that asks for another variant is refused by name."""
    check_linear(cfg)
    for key, want in (("shared_expert_intermediate_size", 0),
                      ("partial_rotary_factor", 1.0)):
        if getattr(cfg, key) != want:
            raise ValueError(f"{key}={getattr(cfg, key)!r} belongs to the "
                             f"hybrid with expert layers (models/gdn_moe."
                             f"py), which num_experts > 0 selects")


def layer_kinds(cfg) -> tuple[str, ...]:
    """One entry a layer: ``"full"`` (K/V rows in whole-context pages)
    where ``(i + 1) % full_attention_interval == 0``, else ``"linear"`` (a
    state slot and a conv tail, no pages)."""
    return tuple("linear" if (li + 1) % cfg.full_attention_interval
                 else "full" for li in range(cfg.num_hidden_layers))


def conv_channels(cfg) -> int:
    return 2 * cfg.linear_num_key_heads * cfg.linear_key_head_dim \
        + cfg.linear_num_value_heads * cfg.linear_value_head_dim


def state_shape(cfg) -> tuple[int, int, int]:
    """One slot's recurrent state in one linear layer (float32): a matrix
    a VALUE head."""
    return (cfg.linear_num_value_heads, cfg.linear_key_head_dim,
            cfg.linear_value_head_dim)


def slot_shape(cfg) -> tuple[int, int]:
    """One slot's recurrent state in one linear layer AS STORED: ``(dk,
    n * dv)`` float32 (module docstring: lane-dense at rest)."""
    n, dk, dv = state_shape(cfg)
    return (dk, n * dv)


def pack_state(s):
    """(B, n, dk, dv) -> the stored layout (B, dk, n * dv)."""
    B, n, dk, dv = s.shape
    return s.transpose(0, 2, 1, 3).reshape(B, dk, n * dv)


def unpack_state(s, n: int):
    """The stored layout (B, dk, n * dv) -> (B, n, dk, dv)."""
    B, dk, width = s.shape
    return s.reshape(B, dk, n, width // n).transpose(0, 2, 1, 3)


def tail_shape(cfg) -> tuple[int, int]:
    """One slot's conv tail in one linear layer (``cfg.dtype``)."""
    return (cfg.linear_conv_kernel_dim - 1, conv_channels(cfg))


def slot_state_bytes(cfg) -> int:
    """Bytes one batch slot holds in ONE linear layer: state + tail."""
    return math.prod(state_shape(cfg)) * 4 \
        + math.prod(tail_shape(cfg)) * jnp.dtype(cfg.dtype).itemsize


def linear_mixer_param_count(cfg) -> int:
    """The leaves :func:`linear_mixer_params` makes."""
    h = cfg.hidden_size
    n, dk, dv = state_shape(cfg)
    return h * (conv_channels(cfg) + n * dv) + n * dv * h \
        + 2 * h * n + 2 * n \
        + cfg.linear_conv_kernel_dim * conv_channels(cfg) + dv


def param_count(cfg) -> int:
    h, hd = cfg.hidden_size, cfg.resolved_head_dim
    nq, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    common = 3 * h * cfg.intermediate_size + 2 * h
    full = common + h * hd * (2 * nq + 2 * nkv) + hd * (nq + nkv)
    linear = common + linear_mixer_param_count(cfg)
    n_full = layer_kinds(cfg).count("full")
    return n_full * full + (cfg.num_hidden_layers - n_full) * linear \
        + 2 * cfg.vocab_size * h + h


# ------------------------------------------------------------------- init

def linear_mixer_params(cfg, tn, uniform, out_std: float) -> dict:
    """A linear layer's mixer leaves (module docstring), drawn through the
    caller's ``tn(shape, std=0.02)`` and ``uniform(shape, lo, hi)``: the
    conv uniform in +-1/sqrt(K); ``exp(A_log)`` uniform in [1, 16] and
    ``softplus(dt_bias)`` log-uniform in [1e-3, 1e-1], so a head's decay a
    token runs from about 0.2 to nearly 1."""
    h = cfg.hidden_size
    n, dk, dv = state_shape(cfg)
    nk, K = cfg.linear_num_key_heads, cfg.linear_conv_kernel_dim
    dt = jnp.exp(uniform((n,), math.log(1e-3), math.log(1e-1)))
    return {"w_q": tn((h, nk * dk)), "w_k": tn((h, nk * dk)),
            "w_v": tn((h, n * dv)), "w_g": tn((h, n * dv)),
            "w_a": tn((h, n)), "w_b": tn((h, n)),
            "A_log": jnp.log(uniform((n,), 1.0, 16.0)).astype(cfg.dtype),
            # softplus^-1(dt)
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(cfg.dtype),
            "conv_w": uniform((K, conv_channels(cfg)), -K ** -0.5,
                              K ** -0.5).astype(cfg.dtype),
            "o_norm": jnp.ones((dv,), cfg.dtype),
            "w_o": tn((n * dv, h), out_std)}


def init_params(key: jax.Array, cfg) -> dict:
    """``transformer.init_params`` for this block: truncated normal 0.02,
    the projections back into the residual stream scaled by
    1/sqrt(2 . layers), norms at one; the linear mixer's leaves as
    :func:`linear_mixer_params` draws them."""
    h, hd = cfg.hidden_size, cfg.resolved_head_dim
    nq, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    F = cfg.intermediate_size
    out_std = 0.02 / math.sqrt(2 * cfg.num_hidden_layers)
    keys = iter(jax.random.split(key, 2 + 13 * cfg.num_hidden_layers))

    def tn(shape, std=0.02):
        return (std * jax.random.truncated_normal(
            next(keys), -2, 2, shape, jnp.float32)).astype(cfg.dtype)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

    ones = lambda *shape: jnp.ones(shape, cfg.dtype)  # noqa: E731

    def layer(kind):
        out = {"post_attn_norm": ones(h), "post_mlp_norm": ones(h),
               "w_gate": tn((h, F)), "w_up": tn((h, F)),
               "w_down": tn((F, h), out_std)}
        if kind == "full":
            return {**out, "wq": tn((h, nq * hd)), "wk": tn((h, nkv * hd)),
                    "wv": tn((h, nkv * hd)), "wo": tn((nq * hd, h), out_std),
                    "q_norm": ones(nq * hd), "k_norm": ones(nkv * hd)}
        return {**out, **linear_mixer_params(cfg, tn, uniform, out_std)}

    return {
        "embed": tn((cfg.vocab_size, h)),
        "layers": tuple(layer(kind) for kind in layer_kinds(cfg)),
        "final_norm": ones(h),
        "lm_head": tn((h, cfg.vocab_size)),
    }


# ------------------------------------------------------ the linear mixer

def causal_conv(u, tail, conv_w, n_valid):
    """The depthwise causal conv over ``u`` (B, S, C) continued from
    ``tail`` (B, K - 1, C), the rows before ``u``'s first.  Returns the
    conv's output (B, S, C) float32 and the new tail: the ``K - 1`` rows
    that end at row ``n_valid`` (B,) of ``u``, so padding rows after a
    prompt's end never enter it (``n_valid`` 0 returns ``tail``)."""
    K, S = conv_w.shape[0], u.shape[1]
    ext = jnp.concatenate([tail.astype(u.dtype), u], axis=1)
    w = conv_w.astype(jnp.float32)
    out = sum(ext[:, j:j + S].astype(jnp.float32) * w[j] for j in range(K))
    new_tail = jax.vmap(lambda e, n: lax.dynamic_slice_in_dim(
        e, n, K - 1, axis=0))(ext, n_valid)
    return out, new_tail


def _l2norm(x):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def linear_inputs(x, layer, tail, valid, *, cfg):
    """The residual stream to what the recurrence takes: ``q``, ``k``
    (B, S, n_k, dk) and ``v`` (B, S, n, dv) float32, normalised and scaled;
    ``g`` = log alpha and ``beta`` (B, S, n) float32, both 0 where ``valid``
    (B, S) is False; and the conv's new tail."""
    from .transformer import _dense
    B, S, _ = x.shape
    n, dk, dv = state_shape(cfg)
    nk = cfg.linear_num_key_heads
    dense = _dense(cfg)
    with scope("lin_conv"):
        u = jnp.concatenate([dense(x, layer["w_q"]), dense(x, layer["w_k"]),
                             dense(x, layer["w_v"])], axis=-1)
        c, new_tail = causal_conv(u, tail, layer["conv_w"],
                                  jnp.sum(valid.astype(jnp.int32), axis=1))
        c = jax.nn.silu(c).astype(x.dtype)
    q = _l2norm(c[..., :nk * dk].reshape(B, S, nk, dk)) * dk ** -0.5
    k = _l2norm(c[..., nk * dk:2 * nk * dk].reshape(B, S, nk, dk))
    v = c[..., 2 * nk * dk:].reshape(B, S, n, dv).astype(jnp.float32)
    a = dense(x, layer["w_a"]).astype(jnp.float32)
    g = -jnp.exp(layer["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        a + layer["dt_bias"].astype(jnp.float32))
    beta = jax.nn.sigmoid(dense(x, layer["w_b"]).astype(jnp.float32))
    if cfg.linear_allow_neg_eigval:
        beta = 2.0 * beta
    keep = valid[..., None]
    return q, k, v, jnp.where(keep, g, 0.0), jnp.where(keep, beta, 0.0), \
        new_tail


_STEP_KERNEL = False


@contextlib.contextmanager
def step_kernel(on: bool):
    """While tracing inside this context, :func:`recurrent_step` is the
    Pallas step kernel (``ops/gdn_step.py``) when ``on``: how the engine's
    decode program says which form it was built with, the function's six
    arguments being all it may take (a planted fault wraps it by that
    signature)."""
    global _STEP_KERNEL
    old, _STEP_KERNEL = _STEP_KERNEL, bool(on)
    try:
        yield
    finally:
        _STEP_KERNEL = old


def step_kernel_engages(n: int, dk: int, dv: int) -> bool:
    """Whether a decode step over states of ``state_shape`` ``(n, dk, dv)``
    that asks for the step kernel gets it: on a TPU for the shapes it
    compiles for, elsewhere always (interpreted).  The engine resolves its
    counter with this and :func:`recurrent_step` its form."""
    from ..ops.gdn_step import step_kernel_takes
    return jax.default_backend() != "tpu" or step_kernel_takes(n, dk, dv)


def recurrent_step(q, k, v, g, beta, state):
    """One token of the recurrence for every slot: q, k (B, n_k, dk), v
    (B, n, dv), g, beta (B, n), ``state`` (B, dk, n * dv) float32, the
    slots as stored (:func:`slot_shape`); value head ``r`` reads key head
    ``r // (n / n_k)``.  Returns ``o`` (B, n, dv) and the new state in
    the same layout.  Elementwise products and sums in
    float32: a state is read as it is stored, never rounded for an MXU
    pass.  ``beta = 0, g = 0`` leaves a state bit for bit as it was.

    Two forms.  Inside :func:`step_kernel` (the engine's decode program
    on a TPU) the Pallas kernel of ``ops/gdn_step.py``: every slot with a
    non-zero ``g`` or ``beta`` read once and written once in place, the
    others not touched (their ``o`` is 0).  Otherwise this XLA form, the
    tests' reference, which passes over every slot three times (read for
    ``S^T k``, read and write for the update) and, on a TPU, first writes
    out k, q and alpha spread over the state's lanes: 25.8 ms a step on
    the v5e at 64 slots of the published widths against the kernel's 4.1
    (PR 30's form over ``(n, dk, dv)`` took 7.5 and a hand-ordered one,
    both reductions from one pass over the OLD state, 7.89: PERF.md)."""
    B, n, dv = v.shape
    dk = q.shape[-1]
    if _STEP_KERNEL and step_kernel_engages(n, dk, dv):
        from ..ops.gdn_step import gdn_decode_step
        return gdn_decode_step(q, k, v, g, beta, state)
    # every state-sized operand in the stored layout (B, dk, n dv): a value
    # head's scalar repeated over its dv lanes, a key head's vector down dk
    # and over the lanes of all its value heads
    lanes = lambda a: jnp.repeat(a, dv, axis=-1)  # noqa: E731
    col = lambda a: jnp.repeat(a.transpose(0, 2, 1),  # noqa: E731
                               n // q.shape[1] * dv, axis=-1)
    s = lanes(jnp.exp(g))[:, None] * state
    u = lanes(beta) * (v.reshape(B, -1) - jnp.sum(col(k) * s, axis=1))
    s = s + col(k) * u[:, None]
    return jnp.sum(col(q) * s, axis=1).reshape(B, n, dv), s


def unit_lower_inverse(A):
    """``(I + A)^-1`` for strictly lower-triangular ``A`` (..., C, C)
    float32, by forward substitution: row ``i`` of the inverse is ``e_i``
    less the rows above it weighted by ``A``'s row ``i``, ``C - 1``
    sequential row updates, each over ALL the systems at once (they lie
    side by side on the last axis, the lanes, a system's own (C, C)
    leading).  Elementwise float32 products and sums: no matrix product, no
    power of ``A``, the operations of a triangular solve against the
    identity in their order (module docstring: how ``T`` is obtained)."""
    C = A.shape[-1]
    L = jnp.moveaxis(A.reshape(-1, C, C), 0, -1)          # (C, C, M)
    index = functools.partial(lax.dynamic_index_in_dim, axis=0,
                              keepdims=False)

    def row(i, T):
        # A[i, j] is 0 from the diagonal on, so the rows of T not yet
        # substituted (still the identity's) add nothing
        above = jnp.sum(index(L, i)[:, None, :] * T, axis=0)
        return lax.dynamic_update_index_in_dim(T, index(T, i) - above, i, 0)

    T = lax.fori_loop(1, C, row, jnp.broadcast_to(
        jnp.eye(C, dtype=A.dtype)[:, :, None], L.shape))
    return jnp.moveaxis(T, -1, 0).reshape(A.shape)


def chunked_scan(q, k, v, g, beta, state):
    """The recurrence over S rows from a carried ``state`` (B, n, dk, dv):
    q, k (B, S, n_k, dk), v (B, S, n, dv), g, beta (B, S, n), all float32.
    Returns ``o`` (B, S, n, dv) and the state after the last row
    (module docstring: the chunked form; ``SCAN_CHUNK`` rows a sub-chunk,
    S padded up to whole sub-chunks with rows that change nothing).  Under
    grouped value heads q and k are repeated a value head first: decay and
    beta are a value head's own, so no triangular system is shared."""
    B, S, n, dv = v.shape
    dk = q.shape[-1]
    if q.shape[2] != n:
        q, k = (jnp.repeat(a, n // a.shape[2], axis=2) for a in (q, k))
    C = min(SCAN_CHUNK, S)
    pad = -S % C
    if pad:
        q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad))
                                    + ((0, 0),) * (a.ndim - 2))
                            for a in (q, k, v, g, beta))
    N = (S + pad) // C
    # (N, B, n, C, .): sub-chunks lead, so the sequential part scans them
    split = lambda a: a.reshape((B, N, C) + a.shape[2:])  # noqa: E731
    q, k, v = (split(a).transpose(1, 0, 3, 2, 4) for a in (q, k, v))
    g, beta = (split(a).transpose(1, 0, 3, 2) for a in (g, beta))
    cum = jnp.cumsum(g, axis=-1)                          # log G_i
    tri = jnp.tril(jnp.ones((C, C), jnp.bool_))
    # D_ij = G_i / G_j for j <= i, 0 above the diagonal (the difference is
    # <= 0 where it is kept: no overflow)
    diff = cum[..., :, None] - cum[..., None, :]
    D = jnp.where(tri, jnp.exp(jnp.where(tri, diff, 0.0)), 0.0)
    kk = jnp.einsum("...ik,...jk->...ij", k, k)
    A = jnp.where(jnp.tril(tri, -1), beta[..., None] * D * kk, 0.0)
    T = unit_lower_inverse(A)
    G = jnp.exp(cum)
    u0 = T @ (beta[..., None] * v)                        # (.., C, dv)
    w = T @ ((beta * G)[..., None] * k)                   # (.., C, dk)
    qk = jnp.einsum("...ik,...jk->...ij", q, k) * D
    G_end = G[..., -1:]
    k_end = k * jnp.exp(cum[..., -1:] - cum)[..., None]   # K * (G_C / G)

    def step(s, xs):
        u0, w, qg, qk, k_end, G_end = xs
        u = u0 - w @ s
        o = qg @ s + qk @ u
        s = G_end[..., None] * s + jnp.swapaxes(k_end, -1, -2) @ u
        return s, o

    state, o = lax.scan(step, state,
                        (u0, w, G[..., None] * q, qk, k_end, G_end))
    o = o.transpose(1, 0, 3, 2, 4).reshape(B, N * C, n, dv)
    return o[:, :S], state


def linear_output(o, x, layer, *, cfg):
    """The heads' outputs ``o`` (B, S, n, dv) float32 through the gated
    per-head norm and ``w_o``: ``Mixer(x)`` of a linear layer."""
    from .transformer import _dense, rms_norm
    B, S = o.shape[:2]
    dense = _dense(cfg)
    gate = jax.nn.silu(dense(x, layer["w_g"]).astype(jnp.float32))
    y = rms_norm(o, layer["o_norm"], cfg.rms_norm_eps).reshape(B, S, -1)
    return dense((y * gate).astype(x.dtype), layer["w_o"])


# ------------------------------------------------- what the block brings
# (to the engine's ``_paged_block_forward``, whose docstring says what a
# block and what a linear mixer bring, and to :func:`hidden_states`)

PAGED_ATTENTION_SCOPE = None


def embed(params, ids, cfg):
    """``embed[ids]``, as it is."""
    return params["embed"].astype(cfg.dtype)[ids]


def attention_scale(cfg) -> None:
    """What the attention scores are multiplied by: None, the paged
    kernels' and the gather path's own ``1/sqrt(head_dim)``."""
    return None


def rope_tables(positions, cfg):
    """No rotary embedding: position reaches the full-attention layers
    through the recurrent ones."""
    return None


def mixer_input(x, layer, *, cfg):
    """What a mixer reads: the residual stream itself (no norm before a
    mixer; in ``mla_moe`` and ``swa_moe``, whose this is too, the
    attention's projections norm their input themselves)."""
    return x


def attention_qkv(x, layer, *, cfg, rope=None):
    """``q`` (B, S, n, hd), ``k``, ``v`` (B, S, n_kv, hd): projections, the
    whole-projection norms of q and k, no rotary embedding; and the heads'
    output gate, which this block has not (None)."""
    from .transformer import _dense, rms_norm
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    dense, eps = _dense(cfg), cfg.rms_norm_eps
    q = rms_norm(dense(x, layer["wq"]), layer["q_norm"], eps)
    k = rms_norm(dense(x, layer["wk"]), layer["k_norm"], eps)
    v = dense(x, layer["wv"])
    return (q.reshape(B, S, cfg.num_attention_heads, hd),
            k.reshape(B, S, cfg.num_key_value_heads, hd),
            v.reshape(B, S, cfg.num_key_value_heads, hd), None)


def add_mixer(x, mixed, layer, *, cfg):
    """``h = x + norm(Mixer(x))``."""
    from .transformer import rms_norm
    return x + rms_norm(mixed, layer["post_attn_norm"], cfg.rms_norm_eps)


def attention_output(attn, gate, x, layer, *, cfg):
    """The heads' outputs ``attn`` (B, S, ..heads.., hd) float32 through
    ``wo`` onto the residual stream: ``h``."""
    from .transformer import _dense
    B, S = attn.shape[:2]
    return add_mixer(x, _dense(cfg)(
        attn.astype(x.dtype).reshape(B, S, -1), layer["wo"]), layer, cfg=cfg)


def linear_mixer_output(o, r, x, layer, *, cfg):
    """A linear layer's ``h`` from the recurrence's outputs ``o``; ``r`` is
    what the mixer read (:func:`mixer_input`)."""
    return add_mixer(x, linear_output(o, r, layer, cfg=cfg), layer, cfg=cfg)


def mlp(h, layer, *, cfg, valid=None):
    """``y = h + norm(MLP(h))``; and the layer's device-side counters,
    which a dense MLP has not (None)."""
    from .transformer import _dense, rms_norm
    dense = _dense(cfg)
    m = dense(jax.nn.silu(dense(h, layer["w_gate"]))
              * dense(h, layer["w_up"]), layer["w_down"])
    return h + rms_norm(m, layer["post_mlp_norm"], cfg.rms_norm_eps), None


def final_norm(x, params, cfg):
    from .transformer import rms_norm
    return rms_norm(x, params["final_norm"], cfg.rms_norm_eps)


# ------------------------------------------------- the cache-less forward

def hidden_states(params, input_ids, cfg):
    """(B, S) ids -> final-norm hidden states (B, S, H): the whole
    sequence at once, the chunked scan from a zero state and an empty
    tail, materialised causal attention, no cache.  For every block whose
    requests keep state slots (``cfg.block_module``, ``cfg.linear_mixer``)."""
    from .transformer import _attention_xla
    blk, lin = cfg.block_module, cfg.linear_mixer
    B, S = input_ids.shape
    with scope("embed"):
        x = blk.embed(params, input_ids, cfg)
        rope = blk.rope_tables(jnp.broadcast_to(jnp.arange(S), (B, S)), cfg)
    valid = jnp.ones((B, S), jnp.bool_)
    scale = blk.attention_scale(cfg) \
        or 1.0 / math.sqrt(cfg.resolved_head_dim)
    for kind, layer in zip(blk.layer_kinds(cfg), params["layers"]):
        if kind == "full":
            with scope("attn_qkv"):
                q, k, v, gate = blk.attention_qkv(
                    blk.mixer_input(x, layer, cfg=cfg), layer, cfg=cfg,
                    rope=rope)
            with scope("attn_core"):
                a = _attention_xla(q, k, v, scale)
            with scope("attn_out"):
                h = blk.attention_output(a, gate, x, layer, cfg=cfg)
        else:
            with scope("attn_qkv"):
                r = blk.mixer_input(x, layer, cfg=cfg)
                *ins, _ = lin.linear_inputs(
                    r, layer, jnp.zeros((B,) + lin.tail_shape(cfg), cfg.dtype),
                    valid, cfg=cfg)
            with scope("attn_core"), scope("lin_scan"):
                o, _ = lin.chunked_scan(*ins, lin.unpack_state(
                    jnp.zeros((B,) + lin.slot_shape(cfg), jnp.float32),
                    lin.state_shape(cfg)[0]))
            with scope("attn_out"):
                h = blk.linear_mixer_output(o, r, x, layer, cfg=cfg)
        with scope("mlp"):
            x, _ = blk.mlp(h, layer, cfg=cfg)
    with scope("loss_head"):
        return blk.final_norm(x, params, cfg)
