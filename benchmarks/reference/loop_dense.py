"""Plain reference of the looped dense decoder (Ouro family, ``model_type``
``ouro``): a stack of dense layers that every token runs ``total_ut_steps``
times with the same weights, the model's final norm and an exit gate at the
end of every pass, and a head that reads the state of ONE pass a token.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernel, no cache (every pass
attends over that pass's own keys of the whole sequence), no batching
trick.  It shares no code with the program under test.  Weights arrive in
the dtype they are served in and are upcast where they are used, a layer at
a time, so the reference fits beside the cell's own state.

``x`` (S, H) is the residual stream; ``T`` = ``total_ut_steps``, ``L`` =
``num_hidden_layers``; ``n`` query heads and ``n_kv`` KV heads of ``hd``;
every norm is an RMSNorm with ``rms_norm_eps``::

    x = E[id]
    for t in 0 .. T-1:                         the SAME L layers every pass
      for l in 0 .. L-1:
        r = norm(x; ln1)
        q, k, v = rope(r wq), rope(r wk), r wv         split-half rotation over the whole
                                                       head, theta rope_theta; no bias
        a = causal softmax(q k^T / sqrt(hd)) v . wo    keys and values of THIS pass
        x = x + norm(a; post_attn_norm)                sandwich norm: a sublayer's output
        m = (silu(r2 w_gate) * (r2 w_up)) w_down,      r2 = norm(x; ln2)
        x = x + norm(m; post_mlp_norm)
      x = norm(x; final_norm)                  the one final norm, at EVERY pass's end
      h_t = x                                  pass t+1 starts from the normed state
      lam_t = sigmoid(w_exit . h_t + b_exit)
    p_t = lam_t prod_{j<t} (1 - lam_j)  (t < T-1);     p_{T-1} = prod_{j<T-1} (1 - lam_j)
    e = min{t : p_0 + ... + p_t >= early_exit_threshold},  T-1 where no sum reaches it
    logits = h_e lm_head

Parameter tree (the program's, ``models/loop_dense.py``): ``embed`` (V, H),
``lm_head`` (H, V), ``final_norm`` (H,), ``exit_gate`` ``{"w": (H,), "b":
(1,)}``, ``layers`` a tuple of one dict a layer: ``ln1``, ``w_qkv`` (H,
(n + 2 n_kv) hd) = ``[wq | wk | wv]`` side by side, ``wo``,
``post_attn_norm``, ``ln2``, ``w_gate``, ``w_up``, ``w_down``,
``post_mlp_norm``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
#: the head is multiplied this many columns of the vocabulary at a time
VOCAB_BLOCK = 8_192


def _up(w):
    return w.astype(F32)


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _up(w)


def _rope(x, theta):
    """x (S, n, hd) at positions 0 .. S-1, split-half convention."""
    S, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(S, dtype=F32)[:, None, None] * inv       # (S, 1, hd/2)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _attention(r, lw, fields, block):
    n = int(fields["num_attention_heads"])
    nkv = int(fields.get("num_key_value_heads") or n)
    S = r.shape[0]
    hd = lw["wo"].shape[0] // n
    theta = float(fields["rope_theta"])
    w = lw["w_qkv"]
    q = _rope((r @ _up(w[:, :n * hd])).reshape(S, n, hd), theta)
    k = _rope((r @ _up(w[:, n * hd:(n + nkv) * hd])).reshape(S, nkv, hd),
              theta)
    v = (r @ _up(w[:, (n + nkv) * hd:])).reshape(S, nkv, hd)
    k, v = (jnp.repeat(a, n // nkv, axis=1) for a in (k, v))
    pos = jnp.arange(S)

    def rows(blk):
        qb, qp = blk
        s = jnp.einsum("qnd,knd->nqk", qb, k) / jnp.sqrt(F32(hd))
        s = jnp.where(pos[None, None, :] <= qp[None, :, None], s, -jnp.inf)
        return jnp.einsum("nqk,knd->qnd", jax.nn.softmax(s, axis=-1), v)

    # queries ``block`` rows at a time, the last block padded with rows that
    # see everything and are dropped
    pad = -S % block
    o = jax.lax.map(rows, (
        jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, block, n, hd),
        jnp.pad(pos, (0, pad), constant_values=S).reshape(-1, block)))
    return o.reshape(S + pad, n * hd)[:S] @ _up(lw["wo"])


def _layer(x, lw, fields, block):
    eps = float(fields["rms_norm_eps"])
    a = _attention(_norm(x, lw["ln1"], eps), lw, fields, block)
    x = x + _norm(a, lw["post_attn_norm"], eps)
    r = _norm(x, lw["ln2"], eps)
    m = (jax.nn.silu(r @ _up(lw["w_gate"])) * (r @ _up(lw["w_up"]))) \
        @ _up(lw["w_down"])
    return x + _norm(m, lw["post_mlp_norm"], eps)


def pass_states(params, ids, fields, block: int | None = None):
    """ids (S,) -> every pass's normed state ``h_t`` (T, S, H) and gate
    ``lam_t`` (T, S), float32."""
    S = ids.shape[0]
    block = min(block or S, S)
    eps = float(fields["rms_norm_eps"])
    gate = params["exit_gate"]

    def one_pass(x, _):
        for lw in params["layers"]:
            x = _layer(x, lw, fields, block)
        h = _norm(x, params["final_norm"], eps)
        lam = jax.nn.sigmoid(h @ _up(gate["w"]) + _up(gate["b"])[0])
        return h, (h, lam)

    _, (hs, lams) = jax.lax.scan(one_pass, _up(params["embed"][ids]), None,
                                 length=int(fields["total_ut_steps"]))
    return hs, lams


def exit_steps(lams, threshold: float):
    """``e`` (S,) from the gates ``lam_t`` (T, S): the first pass at which
    the cumulated exit probability reaches ``threshold``, the last pass
    where none does."""
    T = lams.shape[0]
    survive = jnp.cumprod(1.0 - lams, axis=0)               # prod_{j<=t}
    before = jnp.concatenate([jnp.ones_like(lams[:1]), survive[:-1]])
    p = jnp.concatenate([(lams * before)[:-1], before[-1:]])
    reached = jnp.cumsum(p, axis=0) >= threshold
    return jnp.where(jnp.any(reached, axis=0), jnp.argmax(reached, axis=0),
                     T - 1)


def hidden(params, ids, fields, block: int | None = None):
    """ids (S,) -> the state that reaches the head, ``h_e`` (S, H)."""
    hs, lams = pass_states(params, ids, fields, block)
    e = exit_steps(lams, float(fields.get("early_exit_threshold", 1.0)))
    return jnp.take_along_axis(hs, e[None, :, None], axis=0)[0]


def logits_at(params, ids, positions, fields, block: int = 256):
    """(P, V) float32 logits at ``positions`` (P,) of the sequence ``ids``
    (S,), each against its whole causal context.  Rows after a position
    never reach it, so ``ids`` may be padded at the end to a fixed S."""
    with jax.default_matmul_precision("highest"):
        x = hidden(params, ids, fields, block=block)[positions]
        head = params["lm_head"]
        V = head.shape[1]
        vb = VOCAB_BLOCK if V % VOCAB_BLOCK == 0 else V
        z = jax.lax.map(
            lambda i: x @ _up(jax.lax.dynamic_slice_in_dim(
                head, i * vb, vb, axis=1)),
            jnp.arange(V // vb))
        return z.transpose(1, 0, 2).reshape(x.shape[0], V)
