"""Plain reference of the hybrid decoder of gated delta-rule
(linear-attention) layers and output-gated full-attention layers with an
expert layer under every mixer (Qwen3-Next-80B-A3B's block): embedding, per
layer a mixer and a mixture of experts on a pre-norm residual path with
zero-centred RMSNorms, a final norm, an untied head.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: a linear layer is the
token-by-token recurrence (one ``lax.scan`` over the positions: no
chunking, no carried state, no cache), its conv a sum of shifted copies; a
full-attention layer is causal softmax attention over the whole context,
queries taken ``block`` rows at a time; an expert layer multiplies every
row by every HELD expert and weights the result (no dispatch, no batching
by expert).  It shares no code with the program under test.  Weights
arrive in the dtype they are served in and are upcast one matrix (one
expert, one block of the vocabulary) at a time where they are used, so the
reference fits beside a resident engine.

Equations (``x`` (S, H); eps ``rms_norm_eps``; ``norm(x; w) = x / rms(x) *
(1 + w)``, ZERO-CENTRED, but for ``o_norm``, which is plain ``x / rms(x) *
w``; layer ``i``, 0-based, is a full-attention layer where ``(i + 1) %
full_attention_interval == 0``)::

    h = x + Mixer(norm(x; input_norm));   y = h + MoE(norm(h; post_attn_norm))
    logits = norm(x_L; final_norm) lm_head

    full attention (n heads of hd, n_kv KV heads, rot = partial_rotary_factor hd):
      [q_j | gate_j] = r wq per head j;   k_m, v_m = r wk |_m, r wv |_m
      q_j = rope(norm(q_j; q_norm)),  k_m = rope(norm(k_m; k_norm))     norms over ONE head's hd
      rope: dims 0 .. rot-1 of a head rotated in split-half pairs (i, i + rot/2) by
            angle pos / theta^(2i / rot); dims rot .. hd-1 pass through
      a_j = softmax_{t<=s}(q_j(s) k_{m(j)}(t) / sqrt(hd)) v_{m(j)}(t),   m(j) = j // (n / n_kv)
      Mixer(r) = [a_j * sigmoid(gate_j)]_j wo

    gated delta rule (n_k key heads of dk, n_v value heads of dv, conv width K;
    value head r uses key head r // (n_v / n_k)):
      u = [r w_q | r w_k | r w_v];   c_t = sum_{j<K} conv_w[j] u_{t-K+1+j}   (u_t = 0 for t < 0)
      q~, k~, v~ = silu(c) per head
      q_t = q~_t / sqrt(|q~_t|^2 + 1e-6) / sqrt(dk),  k_t = k~_t / sqrt(|k~_t|^2 + 1e-6),  v_t = v~_t
      beta_t = sigmoid(r_t w_b);   alpha_t = exp(-exp(A_log) softplus(r_t w_a + dt_bias))   per VALUE head
      S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T,  S_0 = 0;   o_t = S_t^T q_t
      Mixer(r)_t = [norm(o_t; o_norm) * silu(r_t w_g)] w_o      norm over a head's dv, plain

    MoE (router width E, k chosen; HELD here: experts e0 .. e0 + held - 1):
      p = softmax(r2 w_router) over all E;  T = top-k(p);  w_e = p_e / sum_{e' in T} p_e'
      MoE(r2) = sum_{e in T, e held} w_e SwiGLU_e(r2) + sigmoid(r2 ws_sigmoid) SwiGLU_shared(r2)
      SwiGLU(r) = (silu(r w_gate) * r w_up) w_down

``w_e`` is normalised over the k CHOSEN experts, held or not; what absent
experts would add is left out, as one rank's part under expert parallelism
is.  With ``num_experts == router_width`` and ``expert_offset`` 0 this is
the uncut layer.

Assumed where the published config is silent (the configuration file lists
them): the split-half pairing of the rotary dims, the 1e-6 under the square
root of q's and k's norms, which norms are zero-centred.

Parameter tree (the program's, ``models/gdn_moe.py``): ``embed`` (V, H),
``lm_head`` (H, V), ``final_norm`` (H,), and ``layers``, a tuple of one
dict a layer, a full-attention one told by its ``wq``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
#: the untied head is multiplied this many columns at a time
VOCAB_BLOCK = 9_496


def _up(w):
    return w.astype(F32)


def _norm(x, w, eps):
    """Zero-centred RMSNorm."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + _up(w))


def _plain_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _up(w)


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


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


def _attention(r, lw, fields, block):
    n = int(fields["num_attention_heads"])
    nkv = int(fields["num_key_value_heads"])
    eps = float(fields["rms_norm_eps"])
    S = r.shape[0]
    hd = lw["wk"].shape[1] // nkv
    qg = (r @ _up(lw["wq"])).reshape(S, n, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    q = _rope(_norm(q, lw["q_norm"], eps), fields)
    k = _rope(_norm((r @ _up(lw["wk"])).reshape(S, nkv, hd), lw["k_norm"],
                    eps), fields)
    v = (r @ _up(lw["wv"])).reshape(S, nkv, hd)
    k, v = (jnp.repeat(a, n // nkv, axis=1) for a in (k, v))
    pos = jnp.arange(S)

    def rows(blk):
        qb, qp = blk
        s = jnp.einsum("qnd,knd->nqk", qb, k) / jnp.sqrt(F32(hd))
        s = jnp.where(pos[None, None, :] <= qp[None, :, None], s, -jnp.inf)
        return jnp.einsum("nqk,knd->qnd", jax.nn.softmax(s, axis=-1), v)

    if S % block:
        block = S
    o = jax.lax.map(rows, (q.reshape(-1, block, n, hd),
                           pos.reshape(-1, block))).reshape(S, n, hd)
    return (o * jax.nn.sigmoid(gate)).reshape(S, -1) @ _up(lw["wo"])


def _delta_rule(r, lw, fields):
    nk = int(fields["linear_num_key_heads"])
    nv = int(fields["linear_num_value_heads"])
    dk = int(fields["linear_key_head_dim"])
    dv = int(fields["linear_value_head_dim"])
    K = int(fields["linear_conv_kernel_dim"])
    eps = float(fields["rms_norm_eps"])
    S = r.shape[0]
    u = jnp.concatenate([r @ _up(lw["w_q"]), r @ _up(lw["w_k"]),
                         r @ _up(lw["w_v"])], axis=-1)
    ext = jnp.concatenate([jnp.zeros((K - 1, u.shape[1]), F32), u])
    w = _up(lw["conv_w"])
    c = jax.nn.silu(sum(ext[j:j + S] * w[j] for j in range(K)))
    q = _unit(c[:, :nk * dk].reshape(S, nk, dk)) / jnp.sqrt(F32(dk))
    k = _unit(c[:, nk * dk:2 * nk * dk].reshape(S, nk, dk))
    v = c[:, 2 * nk * dk:].reshape(S, nv, dv)
    # value head r reads key head r // (nv / nk)
    q, k = (jnp.repeat(a, nv // nk, axis=1) for a in (q, k))
    beta = jax.nn.sigmoid(r @ _up(lw["w_b"]))                      # (S, nv)
    alpha = jnp.exp(-jnp.exp(_up(lw["A_log"])) * jax.nn.softplus(
        r @ _up(lw["w_a"]) + _up(lw["dt_bias"])))

    def token(state, t):
        q_t, k_t, v_t, a_t, b_t = t
        state = a_t[:, None, None] * state
        u_t = b_t[:, None] * (v_t - jnp.einsum("nk,nkv->nv", k_t, state))
        state = state + k_t[:, :, None] * u_t[:, None, :]
        return state, jnp.einsum("nk,nkv->nv", q_t, state)

    _, o = jax.lax.scan(token, jnp.zeros((nv, dk, dv), F32),
                        (q, k, v, alpha, beta))
    gate = jax.nn.silu(r @ _up(lw["w_g"]))
    return (_plain_norm(o, lw["o_norm"], eps).reshape(S, -1) * gate) \
        @ _up(lw["w_o"])


def _swiglu(r, gate, up, down):
    return (jax.nn.silu(r @ _up(gate)) * (r @ _up(up))) @ _up(down)


def moe(r2, lw, fields):
    """The expert layer on the normed rows ``r2`` (S, H): this share's
    routed part, and the gated shared expert.  Returned apart, so that a
    test can add the shares' routed parts and count the shared one once."""
    k = int(fields["num_experts_per_tok"])
    e0 = int(fields.get("expert_offset", 0))
    p = jax.nn.softmax(r2 @ _up(lw["w_router"]), axis=-1)
    top, idx = jax.lax.top_k(p, k)
    w = top / jnp.sum(top, axis=-1, keepdims=True)

    def one(e):
        w_e = jnp.sum(jnp.where(idx == e0 + e, w, 0.0), axis=-1)    # (S,)
        y = _swiglu(r2, lw["we_gate"][e], lw["we_up"][e], lw["we_down"][e])
        return w_e[:, None] * y

    routed = jnp.sum(jax.lax.map(one, jnp.arange(lw["we_gate"].shape[0])),
                     axis=0)
    shared = jax.nn.sigmoid(r2 @ _up(lw["ws_sigmoid"])) \
        * _swiglu(r2, lw["ws_gate"], lw["ws_up"], lw["ws_down"])
    return routed, shared


def _layer(x, lw, fields, block):
    eps = float(fields["rms_norm_eps"])
    r = _norm(x, lw["input_norm"], eps)
    h = x + (_attention(r, lw, fields, block) if "wq" in lw
             else _delta_rule(r, lw, fields))
    routed, shared = moe(_norm(h, lw["post_attn_norm"], eps), lw, fields)
    return h + routed + shared


def hidden(params, ids, fields, block: int | None = None):
    """ids (S,) -> final-norm hidden states (S, H), float32."""
    S = ids.shape[0]
    every = int(fields["full_attention_interval"])
    x = _up(params["embed"][ids])
    for li, lw in enumerate(params["layers"]):
        assert ("wq" in lw) == ((li + 1) % every == 0), li
        x = _layer(x, lw, fields, min(block or S, S))
    return _norm(x, params["final_norm"], float(fields["rms_norm_eps"]))


def logits_at(params, ids, positions, fields, block: int = 256):
    """(P, V) float32 logits at ``positions`` (P,) of the sequence ``ids``
    (S,), each against its whole causal context.  Rows after a position
    never reach it (the recurrence and the conv are causal too), so ``ids``
    may be padded at the end to a fixed S."""
    with jax.default_matmul_precision("highest"):
        x = hidden(params, ids, fields, block=block)[positions]
        head = params["lm_head"]
        H, V = head.shape
        vb = VOCAB_BLOCK if V % VOCAB_BLOCK == 0 else V
        z = jax.lax.map(
            lambda i: x @ _up(jax.lax.dynamic_slice_in_dim(head, i * vb, vb,
                                                           axis=1)),
            jnp.arange(V // vb))
        return z.transpose(1, 0, 2).reshape(x.shape[0], V)
