"""Plain reference of the decoder of compressed convolutional attention
layers over a top-1 expert layer with an MLP router and no shared expert
(ZAYA1-8B's block): embedding, per layer the attention and the expert layer
on a pre-norm residual path, a final norm, the tied head.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: the two convolutions are sums
of shifted copies of the whole sequence, the value shift one shifted copy;
attention is causal softmax attention over the whole context, queries taken
``block`` rows at a time; the expert layer multiplies every row by every
HELD expert and weights the result (no dispatch, no batching by expert).
No cache, no tail, no chunking.  It shares no code with the program under
test.  Weights arrive in the dtype they are served in and are upcast one
matrix (one expert, one block of the vocabulary) at a time where they are
used, so the reference fits beside a resident engine.

Equations (``x`` (S, H); eps ``rms_norm_eps`` in every RMSNorm; n query
heads and n_kv = 2 KV heads of hd; C = (n + n_kv) hd latent channels in
G = n + n_kv groups of hd, the query heads first; ``z_{-1} = z_{-2} =
r_{-1} = 0``)::

    1. r = norm(x; ln1)
    2. q~ = r wq (n hd),  k~ = r wk (n_kv hd),  z = [q~ | k~]                      (S, C)
    3. u_t[c] = a0[c] z_{t-1}[c] + a1[c] z_t[c] + b0[c]          stage 0: depthwise, causal,
                                                                 kernel cca_time0 = 2
    4. y_t[g] = u_{t-1}[g] A0[g] + u_t[g] A1[g] + b1[g]          stage 1: grouped by head, causal,
                   A0[g], A1[g] (hd, hd)                         kernel cca_time1 = 2; the sequence
                                                                 is padded ONCE, with two zero rows
                                                                 before stage 0: u_{-1} = b0, not 0
    5. m_q[h] = (q~[h] + k~[h // (n / n_kv)]) / 2                the q-k mean, of the latents
       m_k[j] = mean over the query heads h of KV head j of m_q[h]
       q' = y_q + m_q,   k' = y_k + m_k
    6. v_t = [r_t wv1 | r_{t-1} wv2]  viewed (2, hd): KV head 0 the token's own values,
                                      KV head 1 the PREVIOUS token's
    7. q'' = sqrt(hd) q' / sqrt(|q'|^2 + 1e-6),   k''_j = tau_j sqrt(hd) k'_j / sqrt(|k'_j|^2 + 1e-6)
                                      over ONE head's hd; tau_j = k_temp[j]
    8. rope on dims 0 .. rot - 1 of a head, rot = partial_rotary_factor hd: split-half pairs
       (i, i + rot / 2) by angle pos / theta^(2 i / rot); dims rot .. hd - 1 pass through
    9. o_h = softmax_{t<=s}(q''_h(s) k''_{m(h)}(t) / sqrt(hd)) v_{m(h)}(t),  m(h) = h // (n / n_kv)
       h = x + [o_h]_h wo
   10. r2 = norm(h; ln2);   s = r2 wr_down;   t1 = gelu(s wr_1 + br_1);   t2 = gelu(t1 wr_2 + br_2)
       p = softmax(t2 wr_3 + br_3) over all E;   e = argmax p          gelu with erf
       x' = h + p_e (silu(r2 G_e) * (r2 U_e)) D_e     the weight is p_e ITSELF: not renormalised

    logits = norm(x_L; final_norm) embed^T                                          tied

HELD here: experts ``expert_offset`` .. ``expert_offset + held - 1`` of the
router's E; a row whose chosen expert is not held adds nothing in step 10
(one rank's part under expert parallelism).  With every expert held this is
the uncut layer.

Assumed where the published config is silent (the configuration file lists
them): steps 3-4's two stages, grouping, biases and single padding; step
5's form; which KV head holds the shifted half; the order norm ->
temperature -> rotary and a temperature a KV head; the router MLP's depth,
erf GELU and biases.

Parameter tree (the program's, ``models/cca_moe.py``): ``embed`` (V, H),
``final_norm`` (H,), and ``layers``, a tuple of one dict a layer: ``ln1``,
``w_qkv`` (H, C + 2 hd) = ``[wq | wk | wv1 | wv2]`` side by side,
``conv0_w`` (2, C) = a0, a1, ``conv0_b`` = b0, ``conv1_w`` (G, 2, hd, hd) =
A0[g], A1[g], ``conv1_b`` (C,) = b1, ``k_temp`` (n_kv,), ``wo``, ``ln2``,
``wr_down``, ``wr_1``, ``br_1``, ``wr_2``, ``br_2``, ``wr_3``, ``br_3``,
``we_gate`` = G, ``we_up`` = U (E, H, F), ``we_down`` = D (E, F, H).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
#: the tied head is multiplied this many rows of the vocabulary at a time
VOCAB_BLOCK = 8_196


def _up(w):
    return w.astype(F32)


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _up(w)


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _shift(a):
    """``a`` (S, ...) one row later, a zero row first: row t holds a_{t-1}."""
    return jnp.concatenate([jnp.zeros_like(a[:1]), a[:-1]])


def _rope(x, fields):
    """x (S, n, hd) at positions 0 .. S-1: the leading rotary dims rotated
    in split-half pairs, the rest untouched."""
    S, _, hd = x.shape
    rot = int(hd * float(fields["partial_rotary_factor"]))
    half = rot // 2
    freq = float(fields["rope_theta"]) ** (-jnp.arange(half, dtype=F32)
                                           * 2.0 / rot)
    ang = jnp.arange(S, dtype=F32)[:, None, None] * freq       # (S, 1, half)
    a, b, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang), rest], -1)


def conv_latents(z, lw, hd: int):
    """Steps 3-4 on the latents ``z`` (S, C): ``y`` (S, G, hd)."""
    S, C = z.shape
    a, b0 = _up(lw["conv0_w"]), _up(lw["conv0_b"])
    u = a[0] * _shift(z) + a[1] * z + b0                     # rows 0 .. S-1
    # the row before the first: stage 0 of the two zero rows
    u_prev = jnp.concatenate([b0[None], u[:-1]])
    A = _up(lw["conv1_w"])                                   # (G, 2, hd, hd)
    group = lambda t: t.reshape(S, C // hd, hd)  # noqa: E731
    return jnp.einsum("sgc,gcd->sgd", group(u_prev), A[:, 0]) \
        + jnp.einsum("sgc,gcd->sgd", group(u), A[:, 1]) \
        + _up(lw["conv1_b"]).reshape(C // hd, hd)


def _attention(r, lw, fields, block):
    n = int(fields["num_attention_heads"])
    nkv = int(fields["num_key_value_heads"])
    S = r.shape[0]
    hd = lw["wo"].shape[0] // n
    rep, C = n // nkv, (n + nkv) * hd
    w = lw["w_qkv"]
    z = r @ _up(w[:, :C])
    y = conv_latents(z, lw, hd)
    zq = z[:, :n * hd].reshape(S, nkv, rep, hd)
    zk = z[:, n * hd:].reshape(S, nkv, 1, hd)
    mq = 0.5 * (zq + zk)
    q = y[:, :n] + mq.reshape(S, n, hd)
    k = y[:, n:] + jnp.mean(mq, axis=2)
    v = jnp.stack([r @ _up(w[:, C:C + hd]),
                   _shift(r @ _up(w[:, C + hd:]))], axis=1)  # (S, 2, hd)
    root = jnp.sqrt(F32(hd))
    q = _rope(root * _unit(q), fields)
    k = _rope(root * _unit(k) * _up(lw["k_temp"])[:, None], fields)
    k, v = (jnp.repeat(a, rep, axis=1) for a in (k, v))
    pos = jnp.arange(S)

    def rows(blk):
        qb, qp = blk
        s = jnp.einsum("qnd,knd->nqk", qb, k) / root
        s = jnp.where(pos[None, None, :] <= qp[None, :, None], s, -jnp.inf)
        return jnp.einsum("nqk,knd->qnd", jax.nn.softmax(s, axis=-1), v)

    if S % block:
        block = S
    o = jax.lax.map(rows, (q.reshape(-1, block, n, hd),
                           pos.reshape(-1, block))).reshape(S, n * hd)
    return o @ _up(lw["wo"])


def router_probs(r2, lw):
    """Step 10's ``p`` (S, E)."""
    gelu = lambda a: jax.nn.gelu(a, approximate=False)  # noqa: E731
    t = gelu((r2 @ _up(lw["wr_down"])) @ _up(lw["wr_1"]) + _up(lw["br_1"]))
    t = gelu(t @ _up(lw["wr_2"]) + _up(lw["br_2"]))
    return jax.nn.softmax(t @ _up(lw["wr_3"]) + _up(lw["br_3"]), axis=-1)


def moe(r2, lw, fields):
    """The expert layer on the normed rows ``r2`` (S, H): this share's part
    of ``p_e SwiGLU_e(r2)``, 0 for a row whose expert is not held here."""
    e0 = int(fields.get("expert_offset", 0))
    p = router_probs(r2, lw)
    chosen = jnp.argmax(p, axis=-1)
    p_e = jnp.max(p, axis=-1)

    def one(e):
        y = (jax.nn.silu(r2 @ _up(lw["we_gate"][e]))
             * (r2 @ _up(lw["we_up"][e]))) @ _up(lw["we_down"][e])
        return jnp.where(chosen == e0 + e, p_e, 0.0)[:, None] * y

    return jnp.sum(jax.lax.map(one, jnp.arange(lw["we_gate"].shape[0])),
                   axis=0)


def _layer(x, lw, fields, block):
    eps = float(fields["rms_norm_eps"])
    h = x + _attention(_norm(x, lw["ln1"], eps), lw, fields, block)
    return h + moe(_norm(h, lw["ln2"], eps), lw, fields)


def hidden(params, ids, fields, block: int | None = None):
    """ids (S,) -> final-norm hidden states (S, H), float32."""
    S = ids.shape[0]
    x = _up(params["embed"][ids])
    for lw in params["layers"]:
        x = _layer(x, lw, fields, min(block or S, S))
    return _norm(x, params["final_norm"], float(fields["rms_norm_eps"]))


def logits_at(params, ids, positions, fields, block: int = 256):
    """(P, V) float32 logits at ``positions`` (P,) of the sequence ``ids``
    (S,), each against its whole causal context.  Rows after a position
    never reach it (the convolutions and the value shift look back only),
    so ``ids`` may be padded at the end to a fixed S."""
    with jax.default_matmul_precision("highest"):
        x = hidden(params, ids, fields, block=block)[positions]
        embed = params["embed"]
        V = embed.shape[0]
        vb = VOCAB_BLOCK if V % VOCAB_BLOCK == 0 else V
        z = jax.lax.map(
            lambda i: x @ _up(jax.lax.dynamic_slice_in_dim(
                embed, i * vb, vb, axis=0)).T,
            jnp.arange(V // vb))
        return z.transpose(1, 0, 2).reshape(x.shape[0], V)
