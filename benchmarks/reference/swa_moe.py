"""Plain reference of the decoder that mixes sliding-window and full GQA
attention layers, output-gated, over leading dense layers and then expert
layers with a sigmoid router, a selection bias, a shared expert and THIS
CHIP'S SHARE of the routed experts (Trinity-Large-Preview's block,
``model_type`` ``afmoe``): scaled embedding, sandwich norms, a final norm,
an untied head.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: attention is causal softmax
attention over the whole sequence, and a window layer's window is a BAND
MASK on it (``t - W < s <= t``): no cache, no ring, no page, no kernel;
every held expert is applied to every token and weighted by the routing.
It shares no code with the program under test.  Weights arrive in the dtype
they are served in and are upcast one matrix (one expert, one block of a
dense layer's columns, one block of the vocabulary) at a time where they
are used, and the scores exist for one KV head's queries, ``block`` rows at
a time, so the reference fits beside a resident engine.

Equations (``x`` (S, H); eps ``rms_norm_eps``; ``norm(x; w) = x / rms(x) *
w``; layer ``i``, 0-based, is a FULL layer where ``(i + 1) %
global_attn_every_n_layers == 0``, else a WINDOW layer of ``sliding_window``
W)::

    x_0 = embed[ids] * sqrt(H)                                   (mup_enabled)
    r = norm(x; ln1);  q, k, v, gate = r wq, r wk, r wv, r wg    n heads of hd, n_kv KV heads
    q_j = norm(q_j; q_norm),  k_m = norm(k_m; k_norm)            over ONE head's hd
    window layer only: q, k = rope(q), rope(k): dims (i, i + hd/2) rotated by
                       angle pos / theta^(2i / hd); a full layer: no rotary
    a_j(t) = softmax_{s in vis(t)}(q_j(t) k_{m(j)}(s) / sqrt(hd)) v_{m(j)}(s),  m(j) = j // (n / n_kv)
             vis(t) = {s <= t} in a full layer, {t - W < s <= t} in a window layer
    x <- x + norm([a_j * sigmoid(gate_j)]_j wo; post_attn_norm)
    r2 = norm(x; ln2)
    dense layer (i < num_dense_layers):  m = (silu(r2 w_gate) * r2 w_up) w_down
    expert layer:  s = sigmoid(r2 w_router);  T = top-k(s + router_bias)
                   w_e = routed_scaling_factor s_e / (sum_{e' in T} s_e' + 1e-20)
                   m = SwiGLU_shared(r2) + sum_{e in T, e held} w_e SwiGLU_e(r2)
    x <- x + norm(m; post_mlp_norm);        logits = norm(x_L; final_norm) lm_head

The bias CHOOSES the experts and is no part of their weights.  Held experts
are ids ``expert_offset`` onwards of the router's ``router_width``; ``w_e``
is normalised over all of ``T``, held here or not, and what absent experts
would add is left out, as one rank's part under expert parallelism is.  With
``num_experts == router_width`` and ``expert_offset`` 0 this is the uncut
layer.

Assumed where the published config is silent (the configuration file lists
them): the embedding's scale, which layers rotate, the split-half pairing,
a separate gate projection as wide as the query's, plain (not zero-centred)
norms, the bias added to the sigmoid scores.

Parameter tree (the program's, ``models/swa_moe.py``): ``embed`` (V, H),
``lm_head`` (H, V), ``final_norm`` (H,), and ``layers``, a tuple of one dict
a layer; an expert layer is told by its ``w_router``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
#: the untied head is multiplied this many columns at a time
VOCAB_BLOCK = 12_512
#: a dense layer's intermediate columns are taken this many at a time
MLP_BLOCK = 3_072


def _up(w):
    return w.astype(F32)


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _up(w)


def _rope(x, theta):
    """x (S, n, hd) at positions 0 .. S-1, split-half pairs."""
    S, _, hd = x.shape
    half = hd // 2
    freq = float(theta) ** (-jnp.arange(half, dtype=F32) * 2.0 / hd)
    ang = jnp.arange(S, dtype=F32)[:, None, None] * freq        # (S, 1, half)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def is_window_layer(li: int, fields) -> bool:
    return (li + 1) % int(fields["global_attn_every_n_layers"]) != 0


def _attention(x, lw, fields, block, window: bool):
    """One KV head's ``rep`` query heads at a time (their columns of ``wq``
    and ``wg``, their rows of ``wo``: the heads' parts of the output
    projection add up), queries ``block`` rows at a time."""
    n = int(fields["num_attention_heads"])
    nkv = int(fields["num_key_value_heads"])
    eps = float(fields["rms_norm_eps"])
    W = int(fields["sliding_window"])
    theta = fields["rope_theta"]
    S = x.shape[0]
    hd = lw["wk"].shape[1] // nkv
    rep = n // nkv
    r = _norm(x, lw["ln1"], eps)
    k = _norm((r @ _up(lw["wk"])).reshape(S, nkv, hd), lw["k_norm"], eps)
    v = (r @ _up(lw["wv"])).reshape(S, nkv, hd)
    if window:
        k = _rope(k, theta)
    pos = jnp.arange(S)
    if S % block:
        block = S

    def group(a, g):
        kg, vg, i = g                                    # (S, hd), (S, hd)
        heads = lambda w, ax: _up(jax.lax.dynamic_slice_in_dim(  # noqa: E731
            w, i * rep * hd, rep * hd, axis=ax))
        qg = _norm((r @ heads(lw["wq"], 1)).reshape(S, rep, hd),
                   lw["q_norm"], eps)
        if window:
            qg = _rope(qg, theta)

        def rows(blk):
            qb, qp = blk                                  # (block, rep, hd)
            s = jnp.einsum("qrd,kd->rqk", qb, kg) / jnp.sqrt(F32(hd))
            vis = pos[None, :] <= qp[:, None]
            if window:
                vis = jnp.logical_and(vis, pos[None, :] > qp[:, None] - W)
            s = jnp.where(vis[None], s, -jnp.inf)
            return jnp.einsum("rqk,kd->qrd", jax.nn.softmax(s, axis=-1), vg)

        o = jax.lax.map(rows, (qg.reshape(-1, block, rep, hd),
                               pos.reshape(-1, block))).reshape(S, rep * hd)
        gated = o * jax.nn.sigmoid(r @ heads(lw["wg"], 1))
        return a + gated @ heads(lw["wo"], 0), None

    a, _ = jax.lax.scan(group, jnp.zeros_like(x),
                        (k.transpose(1, 0, 2), v.transpose(1, 0, 2),
                         jnp.arange(nkv)))
    return x + _norm(a, lw["post_attn_norm"], eps)


def _swiglu(r, gate, up, down):
    return (jax.nn.silu(r @ _up(gate)) * (r @ _up(up))) @ _up(down)


def _dense_mlp(r2, lw):
    """SwiGLU over the intermediate columns a block at a time (the
    columns' parts add up)."""
    F = lw["w_gate"].shape[1]
    fb = MLP_BLOCK if F % MLP_BLOCK == 0 else F

    def cols(m, i):
        sl = lambda w, ax: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            w, i * fb, fb, axis=ax)
        return m + _swiglu(r2, sl(lw["w_gate"], 1), sl(lw["w_up"], 1),
                           sl(lw["w_down"], 0)), None

    return jax.lax.scan(cols, jnp.zeros_like(r2), jnp.arange(F // fb))[0]


def moe(r2, lw, fields):
    """The expert layer on the normed rows ``r2`` (S, H): this share's
    routed part and the shared expert.  Returned apart, so that a test can
    add the shares' routed parts and count the shared one once."""
    k = int(fields["num_experts_per_tok"])
    e0 = int(fields.get("expert_offset", 0))
    s = jax.nn.sigmoid(r2 @ _up(lw["w_router"]))
    _, idx = jax.lax.top_k(s + _up(lw["router_bias"]), k)
    top = jnp.take_along_axis(s, idx, axis=-1)
    w = float(fields["routed_scaling_factor"]) * top \
        / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)

    def add(m, e):
        gate, up, down, i = e
        w_e = jnp.sum(jnp.where(idx == e0 + i, w, 0.0), axis=-1)    # (S,)
        return m + w_e[:, None] * _swiglu(r2, gate, up, down), None

    E = lw["we_gate"].shape[0]
    routed, _ = jax.lax.scan(add, jnp.zeros_like(r2),
                             (lw["we_gate"], lw["we_up"], lw["we_down"],
                              jnp.arange(E)))
    return routed, _swiglu(r2, lw["ws_gate"], lw["ws_up"], lw["ws_down"])


def _layer(x, lw, fields, block, window: bool):
    eps = float(fields["rms_norm_eps"])
    x = _attention(x, lw, fields, block, window)
    r2 = _norm(x, lw["ln2"], eps)
    m = sum(moe(r2, lw, fields)) if "w_router" in lw else _dense_mlp(r2, lw)
    return x + _norm(m, lw["post_mlp_norm"], eps)


def hidden(params, ids, fields, block: int | None = None):
    """ids (S,) -> final-norm hidden states (S, H), float32."""
    S = ids.shape[0]
    x = _up(params["embed"][ids])
    if fields.get("mup_enabled"):
        x = x * jnp.sqrt(F32(x.shape[-1]))
    n_dense = int(fields["num_dense_layers"])
    for li, lw in enumerate(params["layers"]):
        assert ("w_router" in lw) == (li >= n_dense), li
        x = _layer(x, lw, fields, min(block or S, S),
                   is_window_layer(li, fields))
    return _norm(x, params["final_norm"], float(fields["rms_norm_eps"]))


def logits_at(params, ids, positions, fields, block: int = 256):
    """(P, V) float32 logits at ``positions`` (P,) of the sequence ``ids``
    (S,), each against its causal context (its window, in a window layer).
    Rows after a position never reach it, so ``ids`` may be padded at the
    end to a fixed S (and is padded here to whole blocks of query rows)."""
    with jax.default_matmul_precision("highest"):
        # whole blocks of query rows: the rows added lie after every position
        ids = jnp.pad(ids, (0, -ids.shape[0] % min(block, ids.shape[0])))
        x = hidden(params, ids, fields, block=block)[positions]
        head = params["lm_head"]
        H, V = head.shape
        vb = VOCAB_BLOCK if V % VOCAB_BLOCK == 0 else V
        z = jax.lax.map(
            lambda i: x @ _up(jax.lax.dynamic_slice_in_dim(head, i * vb, vb,
                                                           axis=1)),
            jnp.arange(V // vb))
        return z.transpose(1, 0, 2).reshape(x.shape[0], V)
