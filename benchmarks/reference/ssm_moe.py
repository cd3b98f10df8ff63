"""Plain reference of the hybrid decoder of Mamba-2 state-space layers and
GQA attention layers with no position embedding, over routed and shared
experts, with the family's four multipliers (granite-4.0-h-small's block):
a scaled embedding, per layer a mixer and a mixture of experts on a
pre-norm residual path whose branches are scaled, a final norm, the tied
head with scaled logits.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: a Mamba-2 layer is the
token-by-token recurrence (one ``lax.scan`` over the positions: no
chunking, no carried state, no cache), its conv a sum of shifted copies
plus the bias; an attention layer is causal softmax attention over the
whole context at the scale ``attention_multiplier``, queries taken
``block`` rows at a time; an expert layer multiplies every row by every
HELD expert and weights the result (no dispatch, no batching by expert).
It shares no code with the program under test.  Weights arrive in the dtype
they are served in and are upcast one matrix (one expert, one block of the
vocabulary) at a time where they are used, so the reference fits beside a
resident engine.

Equations (``x`` (S, H); ``norm(x; w) = x / rms(x) * w`` with eps
``rms_norm_eps``; ``m_e``, ``m_r``, ``m_a``, ``m_l`` the embedding,
residual and attention multipliers and ``logits_scaling``; layer ``i`` is
an attention layer where ``layer_types[i] == "attention"``)::

    x_0 = embed[ids] * m_e
    h = x + m_r Mixer(norm(x; input_norm));   y = h + m_r (MoE + Shared)(norm(h; post_attn_norm))
    logits = norm(x_L; final_norm) embed^T / m_l

    attention (n heads of hd, n_kv KV heads, NO rotary embedding):
      q, k, v = r wq, r wk, r wv per head
      a_j = softmax_{t<=s}(m_a q_j(s) k_{m(j)}(t)) v_{m(j)}(t),   m(j) = j // (n / n_kv)
      Mixer(r) = [a_j]_j wo

    Mamba-2 (n heads of hd, d = n hd, one B/C group of ds, conv width K over C = d + 2 ds):
      z, u, dt = r w_z, r w_xbc, r w_dt
      c_t = silu(sum_{k<K} conv_w[k] u_{t-K+1+k} + conv_b)      (u_t = 0 for t < 0)
      [xs | B | C] = c_t;   D_t = softplus(dt_t + dt_bias);   a_t = exp(-exp(A_log) D_t)   per head
      S_t[h] = a_t[h] S_{t-1}[h] + D_t[h] xs_t[h] (x) B_t,  S_0 = 0;   o_t[h] = S_t[h] C_t + Dskip[h] xs_t[h]
      Mixer(r)_t = norm(o_t * silu(z_t); gate_norm) w_out        the norm over all d

    MoE (router width E, k chosen; HELD here: experts e0 .. e0 + held - 1):
      T = top-k(r2 w_router);  w = softmax of the CHOSEN logits
      MoE(r2) = sum_{e in T, e held} w_e SwiGLU_e(r2);   Shared(r2) = SwiGLU_shared(r2)
      SwiGLU(r) = (silu(r w_gate) * r w_up) w_down

``w`` is normalised over the k CHOSEN experts, held or not; what absent
experts would add is left out, as one rank's part under expert parallelism
is.  With ``num_local_experts == router_width`` and ``expert_offset`` 0
this is the uncut layer.

Assumed where the published config is silent (the configuration file lists
them): the order gate-then-norm of the gated norm, no clamp on ``dt``, the
state in float32.

Parameter tree (the program's, ``models/ssm_moe.py``): ``embed`` (V, H),
``final_norm`` (H,), and ``layers``, a tuple of one dict a layer, an
attention one told by its ``wq``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
#: the tied head is multiplied this many rows of the vocabulary at a time
VOCAB_BLOCK = 6_272


def _up(w):
    return w.astype(F32)


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _up(w)


def _attention(r, lw, fields, block):
    n = int(fields["num_attention_heads"])
    nkv = int(fields["num_key_value_heads"])
    scale = float(fields["attention_multiplier"])
    S = r.shape[0]
    hd = lw["wk"].shape[1] // nkv
    q = (r @ _up(lw["wq"])).reshape(S, n, hd)
    k = (r @ _up(lw["wk"])).reshape(S, nkv, hd)
    v = (r @ _up(lw["wv"])).reshape(S, nkv, hd)
    k, v = (jnp.repeat(a, n // nkv, axis=1) for a in (k, v))
    pos = jnp.arange(S)

    def rows(blk):
        qb, qp = blk
        s = jnp.einsum("qnd,knd->nqk", qb, k) * scale
        s = jnp.where(pos[None, None, :] <= qp[None, :, None], s, -jnp.inf)
        return jnp.einsum("nqk,knd->qnd", jax.nn.softmax(s, axis=-1), v)

    if S % block:
        block = S
    o = jax.lax.map(rows, (q.reshape(-1, block, n, hd),
                           pos.reshape(-1, block))).reshape(S, n, hd)
    return o.reshape(S, -1) @ _up(lw["wo"])


def _mamba(r, lw, fields):
    n = int(fields["mamba_n_heads"])
    hd = int(fields["mamba_d_head"])
    ds = int(fields["mamba_d_state"])
    K = int(fields["mamba_d_conv"])
    eps = float(fields["rms_norm_eps"])
    S, d = r.shape[0], n * hd
    u = r @ _up(lw["w_xbc"])
    ext = jnp.concatenate([jnp.zeros((K - 1, u.shape[1]), F32), u])
    w = _up(lw["conv_w"])
    c = jax.nn.silu(sum(ext[j:j + S] * w[j] for j in range(K))
                    + _up(lw["conv_b"]))
    xs = c[:, :d].reshape(S, n, hd)
    Bm, Cm = c[:, d:d + ds], c[:, d + ds:]
    dt = jax.nn.softplus(r @ _up(lw["w_dt"]) + _up(lw["dt_bias"]))  # (S, n)
    a = jnp.exp(-jnp.exp(_up(lw["A_log"])) * dt)

    def token(state, t):
        x_t, b_t, c_t, dt_t, a_t = t
        state = a_t[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return state, jnp.einsum("nps,s->np", state, c_t)

    _, o = jax.lax.scan(token, jnp.zeros((n, hd, ds), F32),
                        (xs, Bm, Cm, dt, a))
    o = o + _up(lw["Dskip"])[:, None] * xs
    z = jax.nn.silu(r @ _up(lw["w_z"]))
    return _norm(o.reshape(S, d) * z, lw["gate_norm"], eps) @ _up(lw["w_out"])


def _swiglu(r, gate, up, down):
    return (jax.nn.silu(r @ _up(gate)) * (r @ _up(up))) @ _up(down)


def moe(r2, lw, fields):
    """The expert layer on the normed rows ``r2`` (S, H): this share's
    routed part, and the shared expert.  Returned apart, so that a test
    can add the shares' routed parts and count the shared one once."""
    k = int(fields["num_experts_per_tok"])
    e0 = int(fields.get("expert_offset", 0))
    top, idx = jax.lax.top_k(r2 @ _up(lw["w_router"]), k)
    w = jax.nn.softmax(top, axis=-1)

    def add(e, acc):        # one held expert at a time, onto one sum
        w_e = jnp.sum(jnp.where(idx == e0 + e, w, 0.0), axis=-1)    # (S,)
        y = _swiglu(r2, lw["we_gate"][e], lw["we_up"][e], lw["we_down"][e])
        return acc + w_e[:, None] * y

    routed = jax.lax.fori_loop(0, lw["we_gate"].shape[0], add,
                               jnp.zeros_like(r2))
    return routed, _swiglu(r2, lw["ws_gate"], lw["ws_up"], lw["ws_down"])


def mixer(x, lw, fields, block):
    """``h``: the residual stream after layer ``lw``'s mixer."""
    eps = float(fields["rms_norm_eps"])
    r = _norm(x, lw["input_norm"], eps)
    return x + float(fields["residual_multiplier"]) * (
        _attention(r, lw, fields, block) if "wq" in lw
        else _mamba(r, lw, fields))


def _layer(x, lw, fields, block):
    h = mixer(x, lw, fields, block)
    routed, shared = moe(_norm(h, lw["post_attn_norm"],
                               float(fields["rms_norm_eps"])), lw, fields)
    return h + float(fields["residual_multiplier"]) * (routed + shared)


def hidden(params, ids, fields, block: int | None = None):
    """ids (S,) -> final-norm hidden states (S, H), float32."""
    S = ids.shape[0]
    kinds = list(fields["layer_types"])
    x = _up(params["embed"][ids]) * float(fields["embedding_multiplier"])
    for li, lw in enumerate(params["layers"]):
        assert ("wq" in lw) == (kinds[li] == "attention"), li
        x = _layer(x, lw, fields, min(block or S, S))
    return _norm(x, params["final_norm"], float(fields["rms_norm_eps"]))


def logits_at(params, ids, positions, fields, block: int = 256):
    """(P, V) float32 logits at ``positions`` (P,) of the sequence ``ids``
    (S,), each against its whole causal context.  Rows after a position
    never reach it (the recurrence and the conv are causal too), so ``ids``
    may be padded at the end to a fixed S."""
    with jax.default_matmul_precision("highest"):
        x = hidden(params, ids, fields, block=block)[positions] \
            / float(fields["logits_scaling"])
        head = params["embed"]                      # tied: (V, H)
        V = head.shape[0]
        vb = VOCAB_BLOCK if V % VOCAB_BLOCK == 0 else V
        z = jax.lax.map(
            lambda i: x @ _up(jax.lax.dynamic_slice_in_dim(
                head, i * vb, vb, axis=0)).T,
            jnp.arange(V // vb))
        return z.transpose(1, 0, 2).reshape(x.shape[0], V)
