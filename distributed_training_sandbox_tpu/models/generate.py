"""Autoregressive decoding with a KV cache — the inference face of the
transformer.

The reference is a training course and never decodes (its models run
with ``use_cache=False``, ``fsdp/train_fsdp.py:61-64``); a framework a
user can switch to needs the other half.  TPU-shaped design:

  * the cache is a fixed-capacity pytree of per-layer HEAD-MAJOR
    ``(B, n_kv, S_max, hd)`` buffers — static shapes end to end, so the
    whole decode loop is ONE compiled ``lax.scan`` (no per-token
    retrace, no dynamic shapes);
  * prefill = the normal batched forward (MXU-friendly) that also
    writes the cache via ``lax.dynamic_update_slice``;
  * decode steps run single-query attention against the cache with a
    length mask (positions ≥ the current length contribute nothing);
  * greedy or temperature sampling, PRNG threaded through the scan.

Works under any single-device jit; GQA, RoPE(+NoPE schedule) and the
tied unembedding reuse the training model's code so the two paths
cannot drift.

**int8 decode** (``quantize_decode_params``): decode at real batch sizes
is HBM-bandwidth-bound — every step reads every weight byte.  Weights
are static for the whole generate call, so they are quantized ONCE to
int8 (+ per-column scales) and stored that way; every projection then
reads half the bytes (``ops/quant.QuantizedWeight`` routed through the
same shared ``_dense`` dispatch).  The tied unembedding gets its own
int8 copy (the (H, vocab) matmul is the single largest weight read of a
decode step); the embedding table stays bf16 for the lookup, and norm
scales stay bf16 (negligible bytes, outsized numerics).
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.collectives import axis_size

from . import transformer as T


class KVCache(NamedTuple):
    """Per-layer cache buffers (tuples of L arrays, each HEAD-MAJOR
    (B, n_kv, S_max, hd)) rather than one stacked (L, ...) array: the
    stacked layout made every decode step pay a dynamic-slice COPY of
    each layer's cache (indexing ``cache.k[li]`` inside the layer scan)
    plus a full re-stack into the scan's ys — ~3× the unavoidable
    cache-read traffic, measured as the r4 long-prompt gap (0.50 of
    roofline at prompt 2048).  With per-layer buffers the layer loop is
    unrolled (static layer index), ``dynamic_update_slice`` writes only
    the new token column in place, and the attention einsum reads the
    buffer directly.  HEAD-major (heads before positions) matches the
    attention dot's batch-dim layout — position-major made XLA
    materialize a transposed copy of the whole cache every step (the
    residual bf16 long-prompt gap after the per-layer rewrite).

    ``k_scale``/``v_scale``: per-(batch, head, position) fp32 absmax
    scales when the cache is stored int8 (``quantized=True``) — half the
    cache-read bytes, the decode twin of the int8 weight path; None for
    the bf16 cache."""
    k: tuple          # L × (B, n_kv, S_max, hd) cfg.dtype or int8
    v: tuple          # L × (B, n_kv, S_max, hd)
    k_scale: tuple | None   # L × (B, n_kv, S_max, 1) f32 (int8 only)
    v_scale: tuple | None
    length: jax.Array  # () int32 — tokens currently cached


def init_cache(cfg: T.TransformerConfig, batch: int,
               max_len: int, tp: int = 1,
               quantized: bool = False) -> KVCache:
    """``tp`` > 1: the TENSOR-PARALLEL cache — each rank caches only its
    ``n_kv/tp`` local heads (the KV memory and the per-step cache read
    both shrink by tp, the point of TP-sharded decode)."""
    T.require_dense_block(cfg, "models.generate.init_cache")
    L, nkv, hd = (cfg.num_hidden_layers, cfg.num_key_value_heads,
                  cfg.resolved_head_dim)
    shape = (batch, nkv // tp, max_len, hd)
    dt = jnp.int8 if quantized else cfg.dtype
    zeros = lambda: tuple(jnp.zeros(shape, dt) for _ in range(L))
    scales = lambda: (tuple(jnp.ones(shape[:-1] + (1,), jnp.float32)
                            for _ in range(L)) if quantized else None)
    return KVCache(k=zeros(), v=zeros(), k_scale=scales(),
                   v_scale=scales(), length=jnp.zeros((), jnp.int32))


# Projection leaves quantized for decode; stacked (L, K, N) → per-layer
# scales.  Norm scales (1-D per layer) stay bf16.
_QUANT_LAYER_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def quantize_decode_params(params: dict, cfg: T.TransformerConfig) -> dict:
    """bf16 training params → decode params with every projection weight
    stored int8 (``ops/quant.QuantizedWeight``) and a dedicated int8 copy
    of the unembedding under ``"unembed_q"``.  Quantize once at cache
    build; weight bytes per decode step roughly halve (the decode
    roofline is the weight read).  MoE configs keep their expert banks
    (and router) bf16 — the grouped dispatch inspects weight shapes
    directly; dense projections still quantize."""
    from ..ops.quant import quantize_weight

    layers = dict(params["layers"])
    keys = (_QUANT_LAYER_KEYS if not cfg.n_experts
            else ("wq", "wk", "wv", "wo"))
    for k in keys:
        if k in layers:
            layers[k] = quantize_weight(layers[k], contract_axis=-2)
    out = {**params, "layers": layers}
    # The unembedding matmul is x @ W with W = (H, vocab) — quantize that
    # orientation directly (contraction over H).
    w_vocab = T._output_embedding(params, cfg)          # (vocab, H) rows
    out["unembed_q"] = quantize_weight(w_vocab.T, contract_axis=-2)
    out.pop("lm_head", None)   # superseded by unembed_q for decode
    return out


def _quant_kv(t):
    """Row quantization over the LAST axis: ``(..., D)`` →
    ``(int8 (..., D), f32 (..., 1) scales)`` via the shared symmetric
    absmax quantizer (``ops.quant.quantize_int8``).  Used on head-major
    K/V tensors (rows over hd), on q (rows over hd), and on the
    v-scaled probs (rows over the cache-position axis)."""
    from ..ops.quant import quantize_int8
    return quantize_int8(t, axis=-1)


def _cached_layer_body(x, layer, *, cfg, cos, sin, use_rope,
                       ck, cv, ck_s, cv_s, start, tp_axis=None):
    """One decoder layer that READS/WRITES its cache buffers: the
    training layer's SHARED projection/MLP helpers
    (``transformer._qkv_proj`` / ``_mlp_block`` — one implementation, no
    drift) with attention run against [0, start + S) of the cache
    instead of the local chunk.  x: (B, S, H) with S = prefill length
    or 1.  ``ck``/``cv`` are THIS layer's HEAD-MAJOR
    (B, n_kv, S_max, hd) buffers; ``ck_s``/``cv_s`` their
    (B, n_kv, S_max, 1) int8 row scales or None — updates are single
    in-place ``dynamic_update_slice`` writes of the new token column
    (the stacked-(L, ...) layout's per-step slice copy + restack was
    the r4 long-prompt decode gap; position-major additionally made
    XLA transpose the whole cache for the attention dot each step).

    ``tp_axis``: Megatron tensor-parallel decode (shard_map only) —
    ``layer`` holds this rank's head/intermediate shards
    (``parallel.tensor.tp_specs`` layout), the cache holds only the
    local ``n_kv/tp`` heads, and the two row-parallel outputs are psum'd
    back into the (replicated) residual stream — the same f/g pairing
    the training layer uses (``transformer._layer_body``)."""
    B, S, H = x.shape
    hd = cfg.resolved_head_dim
    tp = axis_size(tp_axis) if tp_axis else 1
    nq, nkv = cfg.num_attention_heads // tp, cfg.num_key_value_heads // tp
    dense = T._dense(cfg)

    r = T.rms_norm(x, layer["ln1"], cfg.rms_norm_eps)
    q, k, v = T._qkv_proj(r, layer, cfg=cfg, cos=cos, sin=sin,
                          use_rope=use_rope, tp=tp)
    # head-major like the cache: (B, S, n_kv, hd) -> (B, n_kv, S, hd) —
    # a tiny S-token transpose instead of a whole-cache one per step
    k = k.transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)

    quantized = ck.dtype == jnp.int8
    if quantized:
        kq, ks_new = _quant_kv(k)
        vq, vs_new = _quant_kv(v)
        ck = lax.dynamic_update_slice(ck, kq, (0, 0, start, 0))
        cv = lax.dynamic_update_slice(cv, vq, (0, 0, start, 0))
        ck_s = lax.dynamic_update_slice(ck_s, ks_new, (0, 0, start, 0))
        cv_s = lax.dynamic_update_slice(cv_s, vs_new, (0, 0, start, 0))
    else:
        ck = lax.dynamic_update_slice(ck, k, (0, 0, start, 0))
        cv = lax.dynamic_update_slice(cv, v, (0, 0, start, 0))

    # attention over the cache: visible = pos_kv <= pos_q (absolute).
    # GQA reads the cache DIRECTLY — grouping the q heads per kv head
    # instead of jnp.repeat'ing (and fp32-upcasting) the cache, which
    # materialized nq/nkv × the KV bytes per step and made long-prompt
    # decode cache-copy-bound (measured 0.17 of roofline at prompt 2048
    # before this).  Scores accumulate in fp32 via
    # preferred_element_type; probs drop to the compute dtype for PV,
    # mirroring the training attention's numerics (_attention_xla).
    # int8 cache: scores contract the int8 codes directly (fp32
    # accumulation) and the per-row K scale — constant over hd, the
    # contracted dim — multiplies the score afterwards, so the HBM read
    # really is int8; the V side folds its scale into the fp32 PV
    # accumulation the same way.
    S_max = ck.shape[2]
    rep = nq // nkv
    qg = q.reshape(B, S, nkv, rep, hd)
    if quantized:
        # TRUE int8 attention: quantize q per row too and contract the
        # int8 CODES on the MXU with int32 accumulation — the cache is
        # read raw (half the bytes), no fp32 upcast copy of it (the
        # upcast-then-dot variant measured SLOWER than the bf16 cache
        # at prompt 2048).  Scales fold outside the contraction: the K
        # row scale is constant over the contracted hd axis, so it
        # multiplies the score afterwards.
        qq, q_s = _quant_kv(qg)                       # rows over hd
        scores_i = jnp.einsum("bsgrh,bgkh->bgrsk", qq, ck,
                              preferred_element_type=jnp.int32)
        scores = (scores_i.astype(jnp.float32)
                  * q_s[..., 0].transpose(0, 2, 3, 1)[..., None]
                  * ck_s[..., 0][:, :, None, None, :]) / math.sqrt(hd)
    else:
        scores = jnp.einsum(
            "bsgrh,bgkh->bgrsk", qg, ck,
            preferred_element_type=jnp.float32) / math.sqrt(hd)
    pos_q = start + jnp.arange(S)
    pos_kv = jnp.arange(S_max)
    vis = pos_kv[None, :] <= pos_q[:, None]          # (S, S_max)
    scores = jnp.where(vis[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    if quantized:
        # fold the per-POSITION V scales into probs (they vary along
        # the contracted axis k), then row-quantize the weighted probs
        # so the V dot also runs int8 × int8 over the raw cache
        pv = probs * cv_s[..., 0][:, :, None, None, :]
        pvq, pv_s = _quant_kv(pv)                     # rows over k
        attn_i = jnp.einsum("bgrsk,bgkh->bsgrh", pvq, cv,
                            preferred_element_type=jnp.int32)
        attn = attn_i.astype(jnp.float32) \
            * pv_s[..., 0].transpose(0, 3, 1, 2)[..., None]
    else:
        attn = jnp.einsum("bgrsk,bgkh->bsgrh", probs.astype(x.dtype), cv,
                          preferred_element_type=jnp.float32)
    attn = attn.astype(x.dtype).reshape(B, S, nq * hd)
    attn_out = dense(attn, layer["wo"])
    if tp_axis:
        from ..ops import collectives as C
        attn_out = C.all_reduce(attn_out, tp_axis)
    x = x + attn_out

    r = T.rms_norm(x, layer["ln2"], cfg.rms_norm_eps)
    mlp, _aux = T._mlp_block(r, layer, cfg=cfg)
    if tp_axis:
        mlp = C.all_reduce(mlp, tp_axis)
    return x + mlp, (ck, cv, ck_s, cv_s)


def _forward_cached(params, ids, cfg, cache: KVCache, start,
                    tp_axis=None):
    """ids (B, S) → (last-position logits (B, V) fp32, cache') using /
    refreshing the cache; ``start`` = absolute position of ids[:, 0].
    Only the LAST position's logits are computed — decoding never needs
    the rest, and a full (B, S, vocab) fp32 prefill buffer would be the
    exact memory spike the streamed training loss exists to avoid.

    The layer loop is UNROLLED (static layer index into the per-layer
    cache buffers): each layer's params are sliced statically from the
    stacked (L, ...) leaves and its cache update is one in-place
    ``dynamic_update_slice`` — no per-step dynamic-slice copy, no
    restack.  Decode-depth models (L ≤ ~36) compile fine unrolled; the
    training path keeps its ``lax.scan``."""
    B, S = ids.shape
    x = params["embed"].astype(cfg.dtype)[ids]
    cos, sin = T._rope_tables(S, cfg.resolved_head_dim, cfg.rope_theta,
                              start)
    # host-side: the unrolled loop needs CONCRETE per-layer flags
    # (T._rope_flags stages jnp ops, which are tracers under this jit)
    flags = [(li + 1) % cfg.nope_interval != 0 if cfg.nope_interval
             else True for li in range(cfg.num_hidden_layers)]

    ks, vs = list(cache.k), list(cache.v)
    kss = list(cache.k_scale) if cache.k_scale is not None else None
    vss = list(cache.v_scale) if cache.v_scale is not None else None
    for li in range(cfg.num_hidden_layers):
        layer = jax.tree.map(lambda p: p[li], params["layers"])
        x, (ks[li], vs[li], ksc, vsc) = _cached_layer_body(
            x, layer, cfg=cfg, cos=cos, sin=sin,
            use_rope=bool(flags[li]),
            ck=ks[li], cv=vs[li],
            ck_s=kss[li] if kss is not None else None,
            cv_s=vss[li] if vss is not None else None,
            start=start, tp_axis=tp_axis)
        if kss is not None:
            kss[li], vss[li] = ksc, vsc
    x = T.rms_norm(x[:, -1:], params["final_norm"], cfg.rms_norm_eps)
    uq = params.get("unembed_q")
    if uq is not None:       # int8 decode: the (H, vocab) read halves
        from ..ops.quant import prequantized_dense
        logits = prequantized_dense(x, uq)[:, 0]
    else:
        logits = (x @ T._output_embedding(params, cfg).T)[:, 0]
    new = KVCache(k=tuple(ks), v=tuple(vs),
                  k_scale=tuple(kss) if kss is not None else None,
                  v_scale=tuple(vss) if vss is not None else None,
                  length=start + S)
    return logits.astype(jnp.float32), new


def _generate_core(params, prompt_ids, rng, cfg: T.TransformerConfig,
                   max_new_tokens: int, temperature: float,
                   tp_axis=None, kv_quant: bool = False,
                   cache_capacity: int | None = None):
    T.require_dense_block(cfg, "models.generate, the one-shot decoder,")
    B, S0 = prompt_ids.shape
    # ``cache_capacity`` pins the attention's contraction extent: XLA's
    # softmax-denominator reduction order depends on the K dimension, so
    # two decodes agree BITWISE only when they contract over the same
    # capacity (masked tail positions contribute exact zeros, but the
    # sum's association differs).  The serving engine always contracts
    # over its fixed page-pool view; parity checks pass the same value
    # here.
    if cache_capacity is not None and cache_capacity < S0 + max_new_tokens:
        raise ValueError(
            f"cache_capacity={cache_capacity} < prompt+new "
            f"({S0}+{max_new_tokens}); the decode would write past it")
    S_max = cache_capacity or (S0 + max_new_tokens)
    tp = axis_size(tp_axis) if tp_axis else 1
    cache = init_cache(cfg, B, S_max, tp=tp, quantized=kv_quant)
    logits, cache = _forward_cached(params, prompt_ids, cfg, cache, 0,
                                    tp_axis=tp_axis)

    def pick(logits_1, key):
        if temperature == 0.0:
            return jnp.argmax(logits_1, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            key, logits_1 / temperature, axis=-1).astype(jnp.int32)

    tok0 = pick(logits, rng)

    def step(carry, key):
        tok, cache = carry
        logits, cache = _forward_cached(params, tok[:, None], cfg,
                                        cache, cache.length,
                                        tp_axis=tp_axis)
        nxt = pick(logits, key)
        return (nxt, cache), nxt

    # max_new_tokens - 1 scanned steps: tok0 came from the prefill
    # logits, and each step emits the token it computes — no wasted
    # final forward (the r3 advisor's finding on this loop).
    keys = jax.random.split(jax.random.fold_in(rng, 1),
                            max_new_tokens - 1)
    (_, _), toks = lax.scan(step, (tok0, cache), keys)
    toks = jnp.concatenate([tok0[None], toks], axis=0)
    return toks.swapaxes(0, 1)   # (B, max_new_tokens)


@partial(jax.jit, static_argnames=("cfg", "max_new_tokens",
                                   "temperature", "kv_quant",
                                   "cache_capacity"))
def generate(params, prompt_ids, cfg: T.TransformerConfig, *,
             max_new_tokens: int = 32, temperature: float = 0.0,
             rng: jax.Array | None = None, kv_quant: bool = False,
             cache_capacity: int | None = None):
    """Decode ``max_new_tokens`` after ``prompt_ids`` (B, S_prompt).

    temperature 0 = greedy argmax; > 0 = categorical sampling — ``rng``
    is then REQUIRED (a silent default key would return identical
    "samples" on every call).  ``kv_quant`` stores the KV cache int8
    with per-row scales — half the cache-read bytes per step, the
    long-prompt lever.  ``cache_capacity`` (static) pads the cache to a
    fixed S_max ≥ prompt+new — the attention then contracts over that
    capacity, which is what makes tokens bitwise-comparable against the
    serving engine's fixed-size paged view (see ``serving.engine``).
    Returns (B, max_new_tokens) int32.  One prefill forward + one
    scanned decode loop — two compiled programs total, static shapes
    throughout.
    """
    if temperature > 0.0 and rng is None:
        raise ValueError("temperature > 0 samples stochastically: pass "
                         "rng=jax.random.PRNGKey(...) explicitly")
    if rng is None:
        rng = jax.random.PRNGKey(0)   # unused by greedy picks
    return _generate_core(params, prompt_ids, rng, _decode_cfg(cfg),
                          max_new_tokens, temperature,
                          kv_quant=kv_quant,
                          cache_capacity=cache_capacity)


def _decode_cfg(cfg: T.TransformerConfig) -> T.TransformerConfig:
    """Decode never checkpoints, so remat knobs must not leak in: a
    save_dots_q8-trained config would otherwise pay the int8 save
    round-trip (noise + cost, zero memory benefit) on every decode
    projection."""
    if cfg.remat:
        import dataclasses
        return dataclasses.replace(cfg, remat=False)
    return cfg


def make_tp_generate(cfg: T.TransformerConfig, mesh, *, axis: str = "tp",
                     max_new_tokens: int = 32, temperature: float = 0.0,
                     kv_quant: bool = False,
                     cache_capacity: int | None = None):
    """TP-sharded decode: ``fn(params_tp, prompt_ids, rng) -> tokens``.

    ``params_tp`` hold Megatron layer shards
    (``parallel.tensor.shard_params_tp``: wq/wk/wv/w_gate/w_up
    column-sharded, wo/w_down row-sharded, embed/norms replicated); the
    KV cache holds only each rank's ``n_kv/tp`` heads, so both the
    weight read AND the cache read of every decode step shrink by tp —
    the multi-chip decode scaling path.  Prompt and emitted tokens are
    replicated (every rank decodes the same stream)."""
    from ..ops import collectives as C
    from ..parallel.tensor import check_tp_divisibility, tp_specs

    check_tp_divisibility(cfg, int(mesh.shape[axis]))
    cfg = _decode_cfg(cfg)

    def core(params, prompt_ids, rng):
        return _generate_core(params, prompt_ids, rng, cfg,
                              max_new_tokens, temperature, tp_axis=axis,
                              kv_quant=kv_quant,
                              cache_capacity=cache_capacity)

    compiled = {}   # built once on first call (specs need a params tree)

    def fn(params_tp, prompt_ids, rng=None):
        if temperature > 0.0 and rng is None:
            raise ValueError("temperature > 0 needs an explicit rng")
        if rng is None:
            rng = jax.random.PRNGKey(0)
        if "jit" not in compiled:
            from jax.sharding import PartitionSpec as P
            compiled["jit"] = jax.jit(C.smap(
                core, mesh,
                in_specs=(tp_specs(params_tp, axis), P(), P()),
                out_specs=P()))
        return compiled["jit"](params_tp, prompt_ids, rng)

    return fn
