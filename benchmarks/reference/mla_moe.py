"""Plain reference of the latent-attention + held-experts decoder
(openPangu-Ultra-MoE-718B's block, of the DeepSeek-V3 family): embedding,
RMSNorm, multi-head latent attention with one shared rotary key, sandwich
norms, leading SwiGLU layers, then expert layers with a sigmoid router, a
shared expert and THIS CHIP'S SHARE of the routed experts, untied head.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: attention is materialised
(the cached latents are never used; keys and values of the whole causal
context are up-projected per head), every held expert is applied to every
token and masked by the routing, no cache, no kernel, no absorbed form.  It
shares no code with the program under test.  Weights arrive in the dtype
they are served in and are upcast one matrix (one expert, one block of the
vocabulary) at a time where they are used, so the reference fits beside a
resident engine.

Equations (``x`` (S, H); ``n`` heads; eps ``rms_norm_eps``)::

    r = norm(x; ln1);  c_q = norm(r w_dq; q_norm);  q = c_q w_uq^T -> n x (q_nope | q_rope)
    [c_kv | k_r] = r w_dkv;  c_kv = norm(c_kv; kv_norm);  k_rope = RoPE(k_r);  q_rope = RoPE(q_rope)
    k_nope_h = c_kv w_uk[h]^T;  v_h = c_kv w_uv[h]
    score_h(i,j) = (q_nope_h(i) k_nope_h(j) + q_rope_h(i) k_rope(j)) / sqrt(nope + rope),  j <= i
    a = concat_h(softmax(score_h) v_h) wo;   x <- x + norm(a; post_attn_norm)
    r2 = norm(x; ln2)
    dense layer:   m = (silu(r2 w_gate) * r2 w_up) w_down
    expert layer:  s = sigmoid(r2 w_router);  T = top-k(s);  w_e = scaling s_e / (sum_T s + 1e-20)
                   m = SwiGLU_shared(r2) + sum_{e in T and held} w_e SwiGLU_e(r2)
    x <- x + norm(m; post_mlp_norm);        logits = norm(x; final_norm) lm_head

Assumed where the published config is silent (the configuration file lists
them): split-half rotary pairing over the ``qk_rope_head_dim`` dims with no
length scaling; sigmoid router scores with no expert groups and no
correction bias.  Held experts are ids ``expert_offset`` onwards of the
router's ``router_width``; ``w_e`` is normalised over all of ``T``, and
what absent experts would add is left out, here as in the program.

Parameter tree (the program's, ``models/mla_moe.py``): ``embed`` (V, H),
``lm_head`` (H, V), ``final_norm`` (H,), and ``layers``, a tuple of one
dict a layer: the first ``first_k_dense_replace`` dense, the rest expert
layers (told apart by their ``w_router``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
#: the untied head is multiplied this many columns at a time
VOCAB_BLOCK = 19_200


def _up(w):
    return w.astype(F32)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _up(w)


def _rope(x, pos, theta):
    """x (S, ..., d), pos (S,) -> rotated, split-half convention."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = pos.astype(F32)[:, None] * inv[None, :]
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(r, gate, up, down):
    return (jax.nn.silu(r @ _up(gate)) * (r @ _up(up))) @ _up(down)


def _attention(x, lw, fields, pos, block):
    n = int(fields["num_attention_heads"])
    dn, dr = int(fields["qk_nope_head_dim"]), int(fields["qk_rope_head_dim"])
    rank = int(fields["kv_lora_rank"])
    eps, theta = float(fields["rms_norm_eps"]), float(fields["rope_theta"])
    S = x.shape[0]
    r = _rms_norm(x, lw["ln1"], eps)
    c_q = _rms_norm(r @ _up(lw["w_dq"]), lw["q_norm"], eps)
    q = (c_q @ _up(lw["w_uq"]).T).reshape(S, n, dn + dr)   # stored out x in
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], pos, theta)
    kv = r @ _up(lw["w_dkv"])
    c_kv = _rms_norm(kv[:, :rank], lw["kv_norm"], eps)
    k_rope = _rope(kv[:, rank:], pos, theta)                       # (S, dr)
    k_nope = jnp.einsum("kc,ndc->knd", c_kv, _up(lw["w_uk"]))
    v = jnp.einsum("kc,nce->kne", c_kv, _up(lw["w_uv"]))

    def rows(blk):
        qn, qr, qp = blk
        s = (jnp.einsum("qnd,knd->nqk", qn, k_nope)
             + jnp.einsum("qnr,kr->nqk", qr, k_rope)) / jnp.sqrt(F32(dn + dr))
        s = jnp.where(pos[None, None, :] <= qp[None, :, None], s, -jnp.inf)
        return jnp.einsum("nqk,kne->qne", jax.nn.softmax(s, axis=-1), v)

    if S % block:
        block = S
    o = jax.lax.map(rows, (q_nope.reshape(-1, block, n, dn),
                           q_rope.reshape(-1, block, n, dr),
                           pos.reshape(-1, block)))
    a = o.reshape(S, -1) @ _up(lw["wo"])
    return x + _rms_norm(a, lw["post_attn_norm"], eps)


def _expert_mlp(r2, lw, fields):
    k = int(fields["num_experts_per_tok"])
    held = int(fields.get("expert_offset", 0)) \
        + jnp.arange(int(fields["n_routed_experts"]))
    s = jax.nn.sigmoid(r2 @ _up(lw["w_router"]))
    top, idx = jax.lax.top_k(s, k)
    w = float(fields["routed_scaling_factor"]) * top \
        / (jnp.sum(top, -1, keepdims=True) + 1e-20)
    # (S, held): the weight of each held expert, 0 where it was not chosen
    w_held = jnp.sum(jnp.where(idx[:, :, None] == held[None, None, :],
                               w[:, :, None], 0.0), axis=1)

    def add(m, e):
        gate, up, down, we = e
        return m + we[:, None] * _swiglu(r2, gate, up, down), None

    shared = _swiglu(r2, lw["ws_gate"], lw["ws_up"], lw["ws_down"])
    m, _ = jax.lax.scan(add, shared, (lw["we_gate"], lw["we_up"],
                                      lw["we_down"], w_held.T))
    return m


def _layer(x, lw, fields, pos, block, expert: bool):
    eps = float(fields["rms_norm_eps"])
    x = _attention(x, lw, fields, pos, block)
    r2 = _rms_norm(x, lw["ln2"], eps)
    m = _expert_mlp(r2, lw, fields) if expert \
        else _swiglu(r2, lw["w_gate"], lw["w_up"], lw["w_down"])
    return x + _rms_norm(m, lw["post_mlp_norm"], eps)


def hidden(params, ids, fields, block: int | None = None):
    """ids (S,) -> final-norm hidden states (S, H), float32; queries are
    taken ``block`` rows at a time (where that divides S) against the whole
    causal context, so S x S scores of all heads never exist at once."""
    S = ids.shape[0]
    pos = jnp.arange(S)
    x = _up(params["embed"][ids])
    for lw in params["layers"]:
        x = _layer(x, lw, fields, pos, min(block or S, S), "w_router" in lw)
    return _rms_norm(x, params["final_norm"], float(fields["rms_norm_eps"]))


def logits_at(params, ids, positions, fields, block: int = 256):
    """(P, V) float32 logits at ``positions`` (P,) of the sequence ``ids``
    (S,), each against its whole causal context.  Rows after a position
    never reach it, so ``ids`` may be padded at the end to a fixed S."""
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
