"""Plain reference of the hybrid decoder of gated delta-rule
(linear-attention) layers and full-attention layers (Olmo-Hybrid-7B's
block): embedding, per layer a mixer and a SwiGLU MLP on the OLMo family's
reordered-norm residual path, a final RMSNorm, an untied head.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: a linear layer is the
token-by-token recurrence (one ``lax.scan`` over the positions: no
chunking, no carried state, no cache), its conv a sum of shifted copies; a
full-attention layer is causal softmax attention over the whole context,
queries taken ``block`` rows at a time.  It shares no code with the program
under test.  Weights arrive in the dtype they are served in and are upcast
one matrix (one block of the vocabulary) at a time where they are used, so
the reference fits beside a resident engine.

Equations (``x`` (S, H); eps ``rms_norm_eps``; layer ``i``, 0-based, is a
full-attention layer where ``(i + 1) % full_attention_interval == 0``)::

    h = x + norm(Mixer(x); post_attn_norm);   y = h + norm(MLP(h); post_mlp_norm)
    MLP(h) = (silu(h w_gate) * h w_up) w_down;   logits = norm(x; final_norm) lm_head

    full attention (n heads of hd, n_kv KV heads, no rotary embedding):
      q = norm(x wq; q_norm),  k = norm(x wk; k_norm)   over the whole projection;  v = x wv
      Mixer(x) = concat_h(softmax_j<=i(q_h(i) k_h(j) / sqrt(hd)) v_h(j)) wo

    gated delta rule (n heads, key dim dk, value dim dv, conv width K):
      u = [x w_q | x w_k | x w_v];   c_t = sum_{j<K} conv_w[j] u_{t-K+1+j}   (u_t = 0 for t < 0)
      q~, k~, v~ = silu(c) per head
      q_t = q~_t / sqrt(|q~_t|^2 + 1e-6) / sqrt(dk),  k_t = k~_t / sqrt(|k~_t|^2 + 1e-6),  v_t = v~_t
      beta_t = 2 sigmoid(x_t w_b);   alpha_t = exp(-exp(A_log) softplus(x_t w_a + dt_bias))
      S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T,  S_0 = 0;   o_t = S_t^T q_t
      Mixer(x)_t = [norm(o_t; o_norm) * silu(x_t w_g)] w_o      norm over a head's dv

Assumed where the published config is silent (the configuration file lists
them): the norms' placement (the OLMo 2/3 family's), QK-norm over the whole
projection, no rotary embedding (the published ``rope_theta`` is null), the
1e-6 under the square root of q's and k's norms.

Parameter tree (the program's, ``models/gdn_hybrid.py``): ``embed`` (V, H),
``lm_head`` (H, V), ``final_norm`` (H,), and ``layers``, a tuple of one
dict a layer, a full-attention one told by its ``wq``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
#: the untied head is multiplied this many columns at a time
VOCAB_BLOCK = 12_544


def _up(w):
    return w.astype(F32)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _up(w)


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _attention(x, lw, fields, block):
    n = int(fields["num_attention_heads"])
    nkv = int(fields.get("num_key_value_heads") or n)
    eps = float(fields["rms_norm_eps"])
    S = x.shape[0]
    hd = lw["wq"].shape[1] // n
    q = _rms_norm(x @ _up(lw["wq"]), lw["q_norm"], eps).reshape(S, n, hd)
    k = _rms_norm(x @ _up(lw["wk"]), lw["k_norm"], eps).reshape(S, nkv, hd)
    v = (x @ _up(lw["wv"])).reshape(S, nkv, hd)
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
                           pos.reshape(-1, block)))
    return o.reshape(S, -1) @ _up(lw["wo"])


def _delta_rule(x, lw, fields):
    n = int(fields["linear_num_key_heads"])
    dk = int(fields["linear_key_head_dim"])
    dv = int(fields["linear_value_head_dim"])
    K = int(fields["linear_conv_kernel_dim"])
    eps = float(fields["rms_norm_eps"])
    S = x.shape[0]
    u = jnp.concatenate([x @ _up(lw["w_q"]), x @ _up(lw["w_k"]),
                         x @ _up(lw["w_v"])], axis=-1)
    ext = jnp.concatenate([jnp.zeros((K - 1, u.shape[1]), F32), u])
    w = _up(lw["conv_w"])
    c = jax.nn.silu(sum(ext[j:j + S] * w[j] for j in range(K)))
    q = _unit(c[:, :n * dk].reshape(S, n, dk)) / jnp.sqrt(F32(dk))
    k = _unit(c[:, n * dk:2 * n * dk].reshape(S, n, dk))
    v = c[:, 2 * n * dk:].reshape(S, n, dv)
    beta = 2.0 * jax.nn.sigmoid(x @ _up(lw["w_b"]))                 # (S, n)
    alpha = jnp.exp(-jnp.exp(_up(lw["A_log"])) * jax.nn.softplus(
        x @ _up(lw["w_a"]) + _up(lw["dt_bias"])))

    def token(state, t):
        q_t, k_t, v_t, a_t, b_t = t
        state = a_t[:, None, None] * state
        u_t = b_t[:, None] * (v_t - jnp.einsum("nk,nkv->nv", k_t, state))
        state = state + k_t[:, :, None] * u_t[:, None, :]
        return state, jnp.einsum("nk,nkv->nv", q_t, state)

    _, o = jax.lax.scan(token, jnp.zeros((n, dk, dv), F32),
                        (q, k, v, alpha, beta))
    gate = jax.nn.silu(x @ _up(lw["w_g"]))
    return (_rms_norm(o, lw["o_norm"], eps).reshape(S, -1) * gate) \
        @ _up(lw["w_o"])


def _layer(x, lw, fields, block):
    eps = float(fields["rms_norm_eps"])
    mixed = _attention(x, lw, fields, block) if "wq" in lw \
        else _delta_rule(x, lw, fields)
    h = x + _rms_norm(mixed, lw["post_attn_norm"], eps)
    m = (jax.nn.silu(h @ _up(lw["w_gate"])) * (h @ _up(lw["w_up"]))) \
        @ _up(lw["w_down"])
    return h + _rms_norm(m, lw["post_mlp_norm"], eps)


def hidden(params, ids, fields, block: int | None = None):
    """ids (S,) -> final-norm hidden states (S, H), float32."""
    S = ids.shape[0]
    every = int(fields["full_attention_interval"])
    x = _up(params["embed"][ids])
    for li, lw in enumerate(params["layers"]):
        assert ("wq" in lw) == ((li + 1) % every == 0), li
        x = _layer(x, lw, fields, min(block or S, S))
    return _rms_norm(x, params["final_norm"], float(fields["rms_norm_eps"]))


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
