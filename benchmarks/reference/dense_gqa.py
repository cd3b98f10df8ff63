"""Plain reference of the dense GQA decoder (SmolLM3 family): embedding,
RMSNorm, grouped-query attention with rotary embeddings on all but every
``nope_interval``-th layer, causal softmax, SwiGLU, tied output head.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernel, no cache, no
batching trick.  It shares no code with the program under test.
Weights arrive in the dtype they are served in and are upcast one layer at
a time inside the layer scan, so the reference fits beside the cell's own
state.  Follows the published modelling code (HF ``modeling_smollm3.py``:
split-half rotation, pre-norm residual blocks).

Parameter tree (the program's, a plain dict of arrays): ``embed`` (V, H),
``final_norm`` (H,), ``layers`` with stacked leaves ``ln1``/``ln2`` (L, H),
``wq`` (L, H, nq·hd), ``wk``/``wv`` (L, H, nkv·hd), ``wo`` (L, nq·hd, H),
``w_gate``/``w_up`` (L, H, F), ``w_down`` (L, F, H).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x (S, n, hd), pos (S,) -> rotated, split-half convention."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _dims(fields):
    h = int(fields["hidden_size"])
    nq = int(fields["num_attention_heads"])
    nkv = int(fields.get("num_key_value_heads") or nq)
    hd = int(fields.get("head_dim") or h // nq)
    return h, nq, nkv, hd


def _layer(x, lw, use_rope, fields, q_pos, kv_x, kv_pos):
    """One layer.  ``x`` (Sq, H) are the query rows at positions ``q_pos``;
    ``kv_x`` (Sk, H) at ``kv_pos`` the rows keys and values come from (the
    same rows for an unblocked forward)."""
    _, nq, nkv, hd = _dims(fields)
    eps, theta = float(fields["rms_norm_eps"]), float(fields["rope_theta"])
    lw = jax.tree.map(lambda a: a.astype(F32), lw)
    r = _rms_norm(x, lw["ln1"], eps)
    rk = _rms_norm(kv_x, lw["ln1"], eps)
    kpos = kv_pos
    q = (r @ lw["wq"]).reshape(-1, nq, hd)
    k = (rk @ lw["wk"]).reshape(-1, nkv, hd)
    v = (rk @ lw["wv"]).reshape(-1, nkv, hd)
    q = jnp.where(use_rope, _rope(q, q_pos, theta), q)
    k = jnp.where(use_rope, _rope(k, kpos, theta), k)
    rep = nq // nkv
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("qnh,knh->nqk", q, k) / jnp.sqrt(F32(hd))
    s = jnp.where(kpos[None, None, :] <= q_pos[None, :, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("nqk,knh->qnh", p, v).reshape(-1, nq * hd)
    x = x + a @ lw["wo"]
    r = _rms_norm(x, lw["ln2"], eps)
    return x + (jax.nn.silu(r @ lw["w_gate"]) * (r @ lw["w_up"])) \
        @ lw["w_down"]


def _rope_flags(fields):
    idx = jnp.arange(int(fields["num_hidden_layers"]))
    n = int(fields.get("nope_interval") or 0)
    return (idx + 1) % n != 0 if n else jnp.ones_like(idx, dtype=bool)


def hidden(params, ids, fields, block: int | None = None):
    """ids (S,) -> final-norm hidden states (S, H), float32.  With
    ``block`` set, every layer runs its queries in blocks of that many rows
    (each block against its whole causal context), so a long context never
    holds S x S scores at once; the result is the same."""
    S = ids.shape[0]
    block = min(block or S, S)
    pos = jnp.arange(S)
    x = params["embed"][ids].astype(F32)

    def body(x, scanned):
        lw, use_rope = scanned
        outs = [_layer(x[s:s + block], lw, use_rope, fields,
                       pos[s:s + block], x[:s + block], pos[:s + block])
                for s in range(0, S, block)]
        return jnp.concatenate(outs, 0), None

    # rematerialised per layer: the same arithmetic, but a backward pass
    # keeps one layer's upcast weights and scores alive, not all of them
    x, _ = jax.lax.scan(jax.checkpoint(body), x,
                        (params["layers"], _rope_flags(fields)))
    return _rms_norm(x, params["final_norm"].astype(F32),
                     float(fields["rms_norm_eps"]))


def loss(params, ids, labels, fields, block: int | None = None):
    """Mean next-token cross-entropy of one sequence.  With ``block`` set
    (it must divide the length) queries and logits are taken that many
    positions at a time, one block after the other, so a long sequence
    never holds S x S scores or S x V logits at once; the result is the
    same."""
    with jax.default_matmul_precision("highest"):
        S = ids.shape[0]
        if not block or S % block:
            block = S
        x = hidden(params, ids, fields, block=block)
        embed = params["embed"].astype(F32)

        def nll_sum(blk):
            xb, lb = blk
            lg = xb @ embed.T
            logz = jax.scipy.special.logsumexp(lg, axis=-1)
            gold = jnp.take_along_axis(lg, lb[:, None], axis=-1)[:, 0]
            return jnp.sum(logz - gold)

        sums = jax.lax.map(nll_sum, (x.reshape(S // block, block, -1),
                                     labels.reshape(S // block, block)))
        return jnp.sum(sums) / S


def logits_at(params, ids, positions, fields, block: int = 1024):
    """(P, V) float32 logits at ``positions`` (P,) of the sequence ``ids``
    (S,), each against its whole causal context.  Rows after a position
    never reach it, so ``ids`` may be padded at the end to a fixed S."""
    with jax.default_matmul_precision("highest"):
        x = hidden(params, ids, fields, block=block)[positions]
        return x @ params["embed"].astype(F32).T


GROUPS = {"embed": ("embed",), "attention": ("wq", "wk", "wv", "wo"),
          "mlp": ("w_gate", "w_up", "w_down"),
          "norms": ("ln1", "ln2", "final_norm")}


def group_sumsq(grads) -> dict:
    """Sum of squares of a gradient tree per parameter group, in float32."""
    flat = {"embed": grads["embed"], "final_norm": grads["final_norm"],
            **grads["layers"]}
    return {g: sum(jnp.sum(jnp.square(flat[k].astype(F32))) for k in keys)
            for g, keys in GROUPS.items()}


def group_norms(grads) -> dict:
    """L2 norm of a gradient tree per parameter group."""
    return {g: jnp.sqrt(v) for g, v in group_sumsq(grads).items()}
