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


def _kv(x, lw, use_rope, fields, pos):
    """Keys and values (S, nkv, hd) of the rows ``x`` (S, H) at ``pos``."""
    _, _, nkv, hd = _dims(fields)
    r = _rms_norm(x, lw["ln1"], float(fields["rms_norm_eps"]))
    k = (r @ lw["wk"]).reshape(-1, nkv, hd)
    v = (r @ lw["wv"]).reshape(-1, nkv, hd)
    return jnp.where(use_rope, _rope(k, pos, float(fields["rope_theta"])),
                     k), v


def _layer(x, lw, use_rope, fields, q_pos, k, v, kpos):
    """One layer.  ``x`` (Sq, H) are the query rows at positions ``q_pos``;
    ``k``, ``v`` (Sk, nkv, hd) at ``kpos`` the keys and values of the rows
    they may attend to (their own among them)."""
    _, nq, nkv, hd = _dims(fields)
    eps, theta = float(fields["rms_norm_eps"]), float(fields["rope_theta"])
    r = _rms_norm(x, lw["ln1"], eps)
    q = (r @ lw["wq"]).reshape(-1, nq, hd)
    q = jnp.where(use_rope, _rope(q, q_pos, theta), q)
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


#: past this many blocks, blocks share a context: see ``hidden``
MAX_CONTEXTS = 8


def hidden(params, ids, fields, block: int | None = None):
    """ids (S,) -> final-norm hidden states (S, H), float32.  With
    ``block`` set, every layer runs its queries in blocks of that many rows
    (each block against its whole causal context), so a long context never
    holds S x S scores at once; the result is the same.  Past
    ``MAX_CONTEXTS`` blocks (of a length they divide), consecutive blocks
    run as one loop against the keys up to the last of them, the later
    ones masked: a few programs to compile instead of one a block."""
    S = ids.shape[0]
    block = min(block or S, S)
    n_blocks = -(-S // block)
    per = -(-n_blocks // MAX_CONTEXTS) if S % block == 0 else 1
    pos = jnp.arange(S)
    x = params["embed"][ids].astype(F32)

    # several blocks: each is rematerialised on its own, so a backward pass
    # holds one block's scores at a time; the weights are upcast once, so
    # the blocks' gradients add up in float32
    remat = jax.checkpoint if n_blocks > 1 else (lambda f, **kw: f)

    def rows(x, k, v, lw, use_rope, s, e):
        """Rows s..e: one block, or ``per`` blocks against the keys up to e."""
        def layer(blk):
            xb, pb = blk
            return _layer(xb, lw, use_rope, fields, pb, k[:e], v[:e], pos[:e])

        if e - s <= block:
            return layer((x[s:e], pos[s:e]))
        out = jax.lax.map(remat(layer), (x[s:e].reshape(-1, block, x.shape[1]),
                                         pos[s:e].reshape(-1, block)))
        return out.reshape(e - s, -1)

    step = block * per
    one = remat(rows, static_argnums=(5, 6)) if per == 1 else rows

    def body(x, scanned):
        lw, use_rope = scanned
        lw = jax.tree.map(lambda a: a.astype(F32), lw)
        k, v = _kv(x, lw, use_rope, fields, pos)
        outs = [one(x, k, v, lw, use_rope, s, min(s + step, S))
                for s in range(0, S, step)]
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

        # several blocks: a backward pass re-derives each block's logits
        if block < S:
            nll_sum = jax.checkpoint(nll_sum)
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
