"""Low-precision (int8) matmul path — the TPU twin of the reference's FP8
benchmark stack (``fp8/fp8_benchmark.py:61-92``: torchao Float8Linear with
dynamic scaling under FSDP2).

v5e has no fp8 units (SURVEY.md §7.3), so the honest low-precision twin is
int8: the MXU multiplies int8×int8 into int32 at twice the bf16 rate.  The
pieces, mirroring torchao's roles:

  * dynamic **per-row absmax scaling** (`quantize_int8`) — the twin of
    Float8Linear's dynamic scaling;
  * `int8_matmul`: XLA path (``lax.dot_general`` with int32 accumulation);
  * `int8_matmul_pallas`: the same contraction as a hand-tiled **Pallas
    kernel** with the dequant fused into the epilogue — the repo's
    native/kernel-level component (runs in interpreter mode off-TPU).
    VERDICT: measured end-to-end twice (r2 and r3, flagship 3B-L8
    seq 8192: 68.9 vs 74.7 TFLOPS/dev in r3) the hand-tiled kernel is
    ~8-9% BEHIND XLA's own int8 dot + fused quantize epilogue, across a
    block-size sweep.  XLA won; the kernel stays as the from-scratch
    teaching artifact and `"int8"` (the XLA path) is the production
    precision;
  * `quantized_dense`: straight-through-estimator linear layer for
    training (forward int8, backward bf16) — what Float8Linear does;
  * `quantized_all_gather`: gather int8 shards + scales and dequantize
    *after* the wire, the twin of torchao's
    ``enable_fsdp_float8_all_gather`` (``fp8_benchmark.py:79-81``) — 4x
    fewer bytes over ICI than a bf16 gather, with a full-precision
    psum_scatter backward.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from . import collectives as C


def quantize_int8(x: jax.Array, axis: int = -1):
    """Symmetric per-row absmax int8 quantization along ``axis`` (the
    contraction dim): returns (q int8, scale f32 with ``axis`` kept at 1).
    """
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale


class QuantizedWeight(NamedTuple):
    """A weight stored AS int8 in HBM (plus its dequant scales) — for
    weight-STATIC uses (decode: weights never change across the whole
    generate call), where the win is not MXU rate but HBM bandwidth:
    every decode step reads every weight byte, so int8 storage halves the
    weight-read-bound step time.  Quantize once (``quantize_weight``),
    then any ``resolve_quantized_dense`` matmul accepts it in place of
    the bf16 array.  ``q``: int8 with the contraction dim where the bf16
    weight had it; ``s``: f32 scales, contraction dim kept at size 1."""
    q: jax.Array
    s: jax.Array


def quantize_weight(w: jax.Array, *, contract_axis: int = -2) -> QuantizedWeight:
    """(…, K, N) bf16 → QuantizedWeight: per-output-column absmax over the
    contraction dim (default: second-minor, the (K, N) layout of every
    projection here; stacked (L, K, N) leaves quantize per layer)."""
    q, s = quantize_int8(w, axis=contract_axis)
    return QuantizedWeight(q=q, s=s)


def _qres_value(y: jax.Array, name: str) -> jax.Array:
    from jax.ad_checkpoint import checkpoint_name
    q, s = quantize_int8(y, axis=-1)
    q = checkpoint_name(q, name)
    s = checkpoint_name(s, name)
    return dequantize(q, s, y.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def quantized_residual(y: jax.Array, name: str = "dot_q8") -> jax.Array:
    """int8 round-trip through a named remat checkpoint — quantized saved
    activations, the ActNN-style attack on the save_dots memory wall
    (r3's binding constraint: save_dots×int8 planned 18.2 GB vs 15.75 GB
    HBM).  Under ``save_only_these_names(name)`` the SAVED tensors are
    the int8 pair (½ the bytes of the bf16 activation + a per-row f32
    scale); every consumer reads the dequantized value, so the producing
    matmul is never recomputed in the backward — save_dots' FLOPs
    savings at roughly half its activation memory.  Cost: forward
    activations carry per-row absmax int8 noise (~0.4% relative), the
    same noise int8 training matmuls already inject at their inputs.

    Backward is straight-through (identity): ``round``'s true derivative
    is zero a.e., which would null every gradient flowing through the
    round-trip — the STE is what makes the saved-quantized trick
    trainable, exactly as in ``quantized_dense``."""
    return _qres_value(y, name)


def _qres_fwd(y, name):
    return _qres_value(y, name), None   # no residual: backward is identity


def _qres_bwd(name, _res, g):
    return (g,)


quantized_residual.defvjp(_qres_fwd, _qres_bwd)


def prequantized_dense(a: jax.Array, w: QuantizedWeight) -> jax.Array:
    """(…, K) · QuantizedWeight(K, N) → (…, N): dynamic per-row activation
    quantize + int8 MXU dot.  The weight arrives int8 from HBM — half the
    bytes of bf16, the decode-bandwidth play."""
    lead = a.shape[:-1]
    a2 = a.reshape(-1, a.shape[-1])
    xq, xs = quantize_int8(a2, axis=-1)
    out = int8_matmul(xq, xs, w.q, w.s.reshape(1, -1), out_dtype=a.dtype)
    return out.reshape(*lead, w.q.shape[-1])


def dequantize(q: jax.Array, scale: jax.Array, dtype=jnp.bfloat16):
    return (q.astype(jnp.float32) * scale).astype(dtype)


# ------------------------------------------------------------------- XLA

def int8_matmul(xq, xs, wq, ws, out_dtype=jnp.bfloat16):
    """(M,K)int8 · (K,N)int8 → (M,N), int32 accumulation on the MXU, scales
    applied in the epilogue.  xs: (M,1) f32, ws: (1,N) f32."""
    acc = lax.dot_general(xq, wq, (((1,), (0,)), ((), ())),
                          preferred_element_type=jnp.int32)
    return (acc.astype(jnp.float32) * xs * ws).astype(out_dtype)


# ---------------------------------------------------------------- pallas

def _pick_block(dim: int, target: int, mult: int) -> int:
    """Largest divisor of ``dim`` that is <= target and a multiple of
    ``mult`` (TPU lowering wants sublane/lane-aligned blocks: second-minor
    % 8, minor % 128 — or the whole dim)."""
    if dim <= target:
        return dim
    b = target - target % mult
    while b >= mult:
        if dim % b == 0:
            return b
        b -= mult
    return dim


def _qmm_kernel(xq_ref, xs_ref, wq_ref, ws_ref, o_ref):
    acc = jnp.dot(xq_ref[...], wq_ref[...],
                  preferred_element_type=jnp.int32)
    o_ref[...] = (acc.astype(jnp.float32) * xs_ref[...] * ws_ref[...]
                  ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("out_dtype", "block_m",
                                             "block_n", "interpret"))
def int8_matmul_pallas(xq, xs, wq, ws, *, out_dtype=jnp.bfloat16,
                       block_m: int | None = None,
                       block_n: int | None = None,
                       interpret: bool = False):
    """Tiled Pallas twin of `int8_matmul`: grid over (M/bm, N/bn), full-K
    int8 blocks in VMEM, int32 MXU accumulation, fused dequant epilogue."""
    from jax.experimental import pallas as pl

    M, K = xq.shape
    K2, N = wq.shape
    assert K == K2, (K, K2)
    # 3: the int8 x block twice over plus Mosaic's staged copy of it
    bm, bn = _auto_blocks(M, K, N, 3, 2, block_m or 256, block_n or 512)
    return pl.pallas_call(
        _qmm_kernel,
        grid=(M // bm, N // bn),
        in_specs=[
            pl.BlockSpec((bm, K), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((K, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        interpret=interpret,
    )(xq, xs, wq, ws)


def _auto_blocks(M: int, K: int, N: int, x_resident: int, w_resident: int,
                 target_m: int, target_n: int,
                 budget: int = 10 << 20) -> tuple[int, int]:
    """Largest (block_m, block_n) ≤ targets whose working set fits VMEM:
    the full-K x block (bm, K) and w block (K, bn), plus scales and the
    f32 accumulator/output tile.  ``x_resident`` / ``w_resident``: bytes
    one element of each block holds in VMEM while the kernel runs — two
    copies of the input dtype (Pallas double-buffers) plus whatever the
    body materializes from it.  ~16 MB/core total; budget leaves headroom
    for Mosaic scratch."""
    candidates_m = [target_m, 512, 256, 128, 64, 32, 16, 8]
    candidates_n = [target_n, 512, 256, 128]
    for tm in candidates_m:
        for tn in candidates_n:
            if tm > target_m or tn > target_n:
                continue
            bm, bn = _pick_block(M, tm, 8), _pick_block(N, tn, 128)
            need = bm * K * x_resident + K * bn * w_resident \
                + 2 * bn * 4 + bm * bn * 4
            if need <= budget:
                return bm, bn
    return _pick_block(M, 8, 8), _pick_block(N, 128, 128)


def _fused_qmm_kernel(x_ref, wq_ref, ws_ref, o_ref):
    """Quantize the activation block IN VMEM (per-row absmax over the full
    K that the block carries), then int8 MXU dot with the pre-quantized
    weight block and a fused dequant epilogue — the activation never makes
    an int8 round-trip through HBM."""
    xf = x_ref[...].astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    xs = jnp.where(amax > 0, amax / 127.0, 1.0)
    xq = jnp.clip(jnp.round(xf / xs), -127, 127).astype(jnp.int8)
    acc = jnp.dot(xq, wq_ref[...], preferred_element_type=jnp.int32)
    o_ref[...] = (acc.astype(jnp.float32) * xs * ws_ref[...]
                  ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("out_dtype", "block_m",
                                             "block_n", "interpret"))
def int8_matmul_pallas_fused(x, wq, ws, *, out_dtype=jnp.bfloat16,
                             block_m: int | None = None,
                             block_n: int | None = None,
                             interpret: bool = False):
    """(M,K)bf16 · (K,N)int8 → (M,N): activation quantize fused into the
    matmul kernel (weights arrive pre-quantized — one pass per step,
    amortized over the whole M grid).  Block sizes default to the largest
    VMEM-fitting tiles (blocks carry full K for exact per-row scales)."""
    from jax.experimental import pallas as pl

    M, K = x.shape
    K2, N = wq.shape
    assert K == K2, (K, K2)
    # + 1: the int8 codes the body quantizes the block into
    bm, bn = _auto_blocks(M, K, N, 2 * x.dtype.itemsize + 1, 2,
                          block_m or 256, block_n or 512)
    return pl.pallas_call(
        _fused_qmm_kernel,
        grid=(M // bm, N // bn),
        in_specs=[
            pl.BlockSpec((bm, K), lambda i, j: (i, 0)),
            pl.BlockSpec((K, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        interpret=interpret,
    )(x, wq, ws)


def _int8_dot(aq, a_scale, bq, b_scale, dims, out_dtype):
    """General int8 dot_general with int32 accumulation; scales must be
    broadcast-compatible with the (batch..., m, n) result."""
    acc = lax.dot_general(aq, bq, dims, preferred_element_type=jnp.int32)
    return (acc.astype(jnp.float32) * a_scale * b_scale).astype(out_dtype)


# ------------------------------------------------------------- training

def resolve_quantized_dense(precision: str, *, fp8_history_len: int = 0):
    """``matmul_precision`` name → ``(a, w) -> out`` matmul, the ONE
    mapping shared by the attention projections (``transformer._dense``)
    and the per-expert MoE matmuls (``parallel.expert.moe_mlp``), so the
    same precision string always selects the same impl everywhere.
    ``"bf16"`` returns a plain matmul.

    ``"fp8"`` / ``"fp8_pallas"`` select the e4m3-forward/e5m2-backward
    recipe (:func:`fp8_dense`, XLA or Pallas forward kernel);
    ``"fp8_delayed"`` additionally routes scaling through the
    ``fp8_history_len``-deep amax history (the config's
    ``fp8_amax_history_len`` axis).

    Every returned matmul also accepts a ``QuantizedWeight`` in the weight
    slot (decode's weight-static int8 storage) and routes it through
    ``prequantized_dense`` — so the decode path can hand pre-quantized
    layer pytrees to the SAME shared projection helpers the training
    model uses."""
    if precision == "bf16":
        base_fn = lambda a, w: a @ w  # noqa: E731
    elif precision.startswith("fp8"):
        impl = {"fp8": "xla", "fp8_delayed": "xla",
                "fp8_pallas": "pallas"}[precision]
        hist = (fp8_history_len or 16) if precision == "fp8_delayed" else 0
        interpret = jax.default_backend() != "tpu"
        base_fn = lambda a, w: fp8_dense(  # noqa: E731
            a, w, impl, interpret, hist)
    else:
        base = precision.removesuffix("_bwd")
        impl = {"int8": "xla", "int8_pallas": "pallas_fused"}[base]
        quantize_bwd = precision.endswith("_bwd")
        interpret = jax.default_backend() != "tpu"
        base_fn = lambda a, w: quantized_dense(  # noqa: E731
            a, w, impl, interpret, quantize_bwd)
    return lambda a, w: (prequantized_dense(a, w)
                         if isinstance(w, QuantizedWeight) else base_fn(a, w))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def quantized_dense(x, w, impl: str = "xla", interpret: bool = False,
                    quantize_bwd: bool = False):
    """Linear layer with int8 forward — the Float8Linear training recipe
    (quantize dynamically, matmul in low precision).  ``x``: (..., K),
    ``w``: (K, N).

    impl: "xla" (lax.dot_general), "pallas" (pre-quantized-operand kernel),
    or "pallas_fused" (activation quantize fused into the kernel).

    quantize_bwd=False: straight-through bf16 backward (fwd-only precision,
    1/3 of the step's matmul FLOPs run at int8 rate).  True: the two
    backward matmuls (dX = g·Wᵀ, dW = Xᵀ·g) also run int8 with fresh
    per-contraction absmax scales — the full torchao dynamic recipe
    (Float8Linear quantizes grad_output to e5m2 for backward; int8 is the
    v5e-native analogue), putting ALL step matmul FLOPs at int8 rate.
    """
    out, _ = _qdense_fwd(x, w, impl, interpret, quantize_bwd)
    return out


def _qdense_fwd(x, w, impl, interpret, quantize_bwd):
    lead = x.shape[:-1]
    K = x.shape[-1]
    x2 = x.reshape(-1, K)
    if impl == "pallas_fused":
        wq, ws = quantize_int8(w, axis=0)
        out = int8_matmul_pallas_fused(x2, wq, ws, out_dtype=x.dtype,
                                       interpret=interpret)
    else:
        xq, xs = quantize_int8(x2, axis=-1)
        wq, ws = quantize_int8(w, axis=0)
        if impl == "pallas":
            out = int8_matmul_pallas(xq, xs, wq, ws, out_dtype=x.dtype,
                                     interpret=interpret)
        else:
            out = int8_matmul(xq, xs, wq, ws, out_dtype=x.dtype)
    return out.reshape(*lead, w.shape[1]), (x, w)


def _qdense_bwd(impl, interpret, quantize_bwd, res, g):
    x, w = res
    if not quantize_bwd:
        gx = jnp.einsum("...n,kn->...k", g, w)
        gw = jnp.einsum("...k,...n->kn", x, g)
        return gx, gw
    lead = x.shape[:-1]
    K, N = w.shape
    g2 = g.reshape(-1, N)
    x2 = x.reshape(-1, K)
    # dX = g · Wᵀ, contraction over N: g rows / w along its N axis.
    gq, gs = quantize_int8(g2, axis=-1)                 # (M,N), (M,1)
    wq_n, ws_n = quantize_int8(w, axis=1)               # (K,N), (K,1)
    gx = _int8_dot(gq, gs, wq_n, ws_n.T, (((1,), (1,)), ((), ())),
                   x.dtype)                             # (M,K)
    # dW = Xᵀ · g, contraction over M: both quantized along M.
    xq_m, xs_m = quantize_int8(x2, axis=0)              # (M,K), (1,K)
    gq_m, gs_m = quantize_int8(g2, axis=0)              # (M,N), (1,N)
    gw = _int8_dot(xq_m, xs_m.T, gq_m, gs_m, (((0,), (0,)), ((), ())),
                   w.dtype)                             # (K,N)
    return gx.reshape(*lead, K), gw


quantized_dense.defvjp(_qdense_fwd, _qdense_bwd)


# ----------------------------------------------------- quantized gather

@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def quantized_all_gather(x, axis_name: str, axis: int = 0,
                         q8_bwd: bool = False):
    """All-gather a shard in int8 + per-row scales, dequantize after the
    wire: the twin of torchao's fp8 all-gather under FSDP2
    (``fp8_benchmark.py:79-81``; EQuARX explores the same trade for XLA).
    Backward is a full-precision psum_scatter (mean-free sum), matching
    the plain all_gather transpose — unless ``q8_bwd``, which quantizes
    the gradient reduce-scatter too (:func:`quantized_reduce_scatter`),
    putting BOTH directions of FSDP param traffic on int8 wire bytes
    (the full EQuARX trade; grads then carry the documented
    half-quantum-per-contribution error)."""
    out, _ = _qag_fwd(x, axis_name, axis, q8_bwd)
    return out


def _qag_fwd(x, axis_name, axis, q8_bwd=False):
    if x.ndim == 1:
        # 1-D leaf (e.g. a norm scale): one scalar scale per shard,
        # re-applied segment-wise after the gather.
        ws = C.axis_size(axis_name)
        n = x.shape[0]
        q, s = quantize_int8(x.reshape(1, n), axis=-1)  # s: (1, 1)
        qg = C.all_gather(q.reshape(n), axis_name, axis=0)       # (ws*n,)
        sg = C.all_gather(s.reshape(1), axis_name, axis=0)       # (ws,)
        out = (qg.reshape(ws, n).astype(jnp.float32)
               * sg[:, None]).reshape(-1).astype(x.dtype)
        return out, None
    # quantize along some dim that is NOT the gather dim, so the gathered
    # scales stay broadcast-compatible with the gathered int8 data (each
    # shard's scales travel with it over the wire).
    qaxis = -1 if axis != x.ndim - 1 and axis != -1 else 0
    q, s = quantize_int8(x, axis=qaxis)
    qg = C.all_gather(q, axis_name, axis=axis)
    sg = C.all_gather(s, axis_name, axis=axis)
    return dequantize(qg, sg, x.dtype), None


def _qag_bwd(axis_name, axis, q8_bwd, res, g):
    # the gathered output has x's dtype, so g.dtype == x.dtype
    if q8_bwd:
        return (quantized_reduce_scatter(
            g.astype(jnp.float32), axis_name,
            axis=0 if g.ndim == 1 else axis).astype(g.dtype),)
    return (C.reduce_scatter(g.astype(jnp.float32), axis_name,
                             axis=axis).astype(g.dtype),)


quantized_all_gather.defvjp(_qag_fwd, _qag_bwd)


# --------------------------------------------- quantized all-reduce (EQuARX)

@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def quantized_all_reduce(x, axis_name: str):
    """EQuARX-style two-shot quantized all-reduce (arXiv:2506.17615):
    each rank ships its partial sum as int8 codes + per-row f32 scales,
    every rank dequantizes and sums the contributions in rank order.
    ~4x fewer bus bytes than an f32 psum (int8 codes dominate; scales are
    1/row), generalizing ``ddp.quantized_bucket_all_reduce``'s trick from
    DDP grad buckets to TP rejoin and FSDP grad traffic.

    Error bound: each rank's contribution carries symmetric-round error
    ≤ half its quantum (scale/2 per element), so the summed result is
    within ``n_ranks * max_scale / 2`` of ``lax.psum`` element-wise —
    the documented per-contribution bound the tests assert.

    Backward is pinned to psum's own transpose (a full-precision psum of
    the cotangent), so only forward traffic is quantized — the same
    asymmetry as ``quantized_all_gather``."""
    out, _ = _qar_fwd(x, axis_name)
    return out


def _qar_quant(x):
    """Per-row int8 codes + scales for an arbitrary-rank tensor: rows are
    the last axis (a 0/1-D leaf quantizes as one row with one scale)."""
    x_ = x.reshape(1, -1) if x.ndim < 2 else x
    q, s = quantize_int8(x_, axis=-1)
    return q, s


def _qar_fwd(x, axis_name):
    q, s = _qar_quant(x)
    # two-shot: gather every rank's codes and scales (a new leading rank
    # axis), dequantize-and-sum locally in rank order — deterministic
    # reduction order, identical on every rank.
    qg = C.all_gather(q, axis_name, axis=0, tiled=False)
    sg = C.all_gather(s, axis_name, axis=0, tiled=False)
    out = jnp.sum(qg.astype(jnp.float32) * sg, axis=0)
    return out.reshape(x.shape).astype(x.dtype), None


def _qar_bwd(axis_name, _res, g):
    # lax.psum transposes to lax.psum: keep the quantized variant's
    # backward identical to the baseline all-reduce's.
    return (lax.psum(g, axis_name),)


quantized_all_reduce.defvjp(_qar_fwd, _qar_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def quantized_reduce_scatter(x, axis_name: str, axis: int = 0):
    """Two-shot quantized reduce-scatter — the FSDP grad-traffic leg of
    the EQuARX trade: each rank quantizes its full partial tensor (int8
    codes + per-row scales), an all_to_all routes chunk ``r`` of every
    rank to rank ``r``, and the receiver dequantizes and sums its chunk
    in rank order.  Same per-contribution half-quantum error bound as
    :func:`quantized_all_reduce`; backward pinned to the monolithic
    reduce-scatter's transpose (a full-precision all_gather)."""
    out, _ = _qrs_fwd(x, axis_name, axis)
    return out


def _qrs_fwd(x, axis_name, axis):
    n = C.axis_size(axis_name)
    if x.ndim == 1:
        # 1-D leaf: one scalar scale per rank, codes scattered by chunk.
        if x.shape[0] % n:
            raise ValueError(f"quantized_reduce_scatter: dim of size "
                             f"{x.shape[0]} not divisible by axis "
                             f"{axis_name!r} size {n}")
        q, s = quantize_int8(x.reshape(1, -1), axis=-1)     # s: (1, 1)
        qt = C.all_to_all(q.reshape(n, -1), axis_name, split_axis=0,
                          concat_axis=0, tiled=False)        # (n, chunk)
        sg = C.all_gather(s.reshape(1), axis_name, axis=0,
                          tiled=False)                       # (n, 1)
        out = jnp.sum(qt.astype(jnp.float32) * sg, axis=0)
        return out.reshape(-1).astype(x.dtype), None
    axis = axis % x.ndim
    if x.shape[axis] % n:
        raise ValueError(f"quantized_reduce_scatter: dim {axis} of size "
                         f"{x.shape[axis]} not divisible by axis "
                         f"{axis_name!r} size {n}")
    # quantize along a dim that is NOT the scatter dim so each chunk's
    # scales travel with its codes through the same all_to_all
    qaxis = -1 if axis != x.ndim - 1 else 0
    q, s = quantize_int8(x, axis=qaxis)

    def route(t):
        # rank-chunks of the scatter dim onto a new leading axis, then
        # one all_to_all: rank r ends up holding every rank's chunk r,
        # leading axis indexing the SOURCE rank (rank-order sum below)
        c = t.shape[axis] // n
        tr = t.reshape(t.shape[:axis] + (n, c) + t.shape[axis + 1:])
        tr = jnp.moveaxis(tr, axis, 0)
        return C.all_to_all(tr, axis_name, split_axis=0, concat_axis=0,
                            tiled=False)

    out = jnp.sum(route(q).astype(jnp.float32) * route(s), axis=0)
    return out.astype(x.dtype), None


def _qrs_bwd(axis_name, axis, _res, g):
    if g.ndim == 1:
        return (C.all_gather(g, axis_name, axis=0),)
    return (C.all_gather(g, axis_name, axis=axis % g.ndim),)


quantized_reduce_scatter.defvjp(_qrs_fwd, _qrs_bwd)


# ------------------------------------------------------------------- fp8
#
# The other half of the reference's torchao sweep: real fp8 recipes
# (``fp8/fp8_benchmark.py``: Float8Linear, e4m3 forward operands, e5m2
# grad_output in backward, per-tensor dynamic or delayed amax scaling).
# v5e still has no fp8 MXU mode, so like the int8 tier this ships as a
# recipe-faithful CPU-tier implementation: operands make a REAL fp8
# round-trip (jnp.float8_e4m3fn / float8_e5m2 storage — the quantization
# noise is exactly fp8's), accumulation runs f32.  On fp8-capable
# hardware the explicit upcast before the dot becomes a native fp8
# ``dot_general`` — a one-line swap the RESULTS.md caveat records.

FP8_FWD_DTYPE = jnp.float8_e4m3fn   # forward operands  (finfo max 448)
FP8_BWD_DTYPE = jnp.float8_e5m2     # grad_output       (finfo max 57344)


def fp8_max(dtype) -> float:
    """Largest finite value of an fp8 dtype (448 for e4m3fn, 57344 for
    e5m2) — the denominator of per-tensor absmax scaling."""
    return float(jnp.finfo(dtype).max)


def amax_history_update(history: jax.Array, x: jax.Array) -> jax.Array:
    """Delayed-scaling bookkeeping: shift the tensor's current absmax
    into the rolling (H,) f32 history (oldest entry drops off)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)))
    return jnp.concatenate([history[1:], amax[None]])


def scale_from_history(history: jax.Array, dtype) -> jax.Array:
    """Delayed scaling's scale choice: absmax over the whole rolling
    history (torchao's ``delayed`` recipe) rather than just the current
    tensor — robust to single-step amax spikes."""
    amax = jnp.max(history)
    return jnp.where(amax > 0, amax / fp8_max(dtype), 1.0)


def quantize_fp8(x: jax.Array, dtype=FP8_FWD_DTYPE, *,
                 amax_history_len: int = 0):
    """Per-TENSOR absmax scaling to fp8 (Float8Linear's granularity —
    coarser than the int8 tier's per-row scales): returns
    ``(q fp8, scale f32 scalar)`` with ``dequant = q * scale``.

    ``amax_history_len > 0`` routes the scale through the delayed-scaling
    helpers.  This stateless CPU-tier instantiation seeds the history
    with the current tensor's absmax (numerically identical to dynamic
    scaling); a stateful trainer threads a real rolling history through
    its train state and gets genuine delayed scaling from the same two
    helpers."""
    if amax_history_len:
        hist = amax_history_update(
            jnp.zeros((amax_history_len,), jnp.float32), x)
        scale = scale_from_history(hist, dtype)
    else:
        amax = jnp.max(jnp.abs(x.astype(jnp.float32)))
        scale = jnp.where(amax > 0, amax / fp8_max(dtype), 1.0)
    fmax = fp8_max(dtype)
    q = jnp.clip(x.astype(jnp.float32) / scale, -fmax, fmax).astype(dtype)
    return q, scale


def fp8_matmul(aq, a_scale, bq, b_scale, dims, out_dtype):
    """Scaled dot over fp8-quantized operands, f32 accumulation, scalar
    dequant epilogue.  The operands already carry fp8 round-trip noise;
    the upcast before the dot is the CPU-tier stand-in for a native fp8
    ``dot_general`` (see the section comment)."""
    acc = lax.dot_general(aq.astype(jnp.float32), bq.astype(jnp.float32),
                          dims, preferred_element_type=jnp.float32)
    return (acc * a_scale * b_scale).astype(out_dtype)


def _fp8_mm_kernel(aq_ref, as_ref, bq_ref, bs_ref, o_ref):
    acc = jnp.dot(aq_ref[...].astype(jnp.float32),
                  bq_ref[...].astype(jnp.float32),
                  preferred_element_type=jnp.float32)
    o_ref[...] = (acc * as_ref[0, 0] * bs_ref[0, 0]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("out_dtype", "block_m",
                                             "block_n", "interpret"))
def fp8_matmul_pallas(aq, a_scale, bq, b_scale, *, out_dtype=jnp.bfloat16,
                      block_m: int | None = None,
                      block_n: int | None = None,
                      interpret: bool = False):
    """Tiled Pallas twin of :func:`fp8_matmul` (2-D operands, per-tensor
    scalar scales passed as (1, 1) blocks) — the fp8 leg of the kernel
    tier, grid/BlockSpec structure of ``int8_matmul_pallas``."""
    from jax.experimental import pallas as pl

    M, K = aq.shape
    K2, N = bq.shape
    assert K == K2, (K, K2)
    # + 4: the body upcasts both fp8 blocks to float32 before the dot
    bm, bn = _auto_blocks(M, K, N, 2 + 4, 2 + 4,
                          block_m or 256, block_n or 512)
    return pl.pallas_call(
        _fp8_mm_kernel,
        grid=(M // bm, N // bn),
        in_specs=[
            pl.BlockSpec((bm, K), lambda i, j: (i, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((K, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        interpret=interpret,
    )(aq, a_scale.reshape(1, 1), bq, b_scale.reshape(1, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def fp8_dense(x, w, impl: str = "xla", interpret: bool = False,
              amax_history_len: int = 0):
    """Linear layer with the Float8Linear recipe end-to-end: e4m3
    per-tensor-scaled operands forward, and a backward whose THREE
    operands split by role exactly as torchao's — grad_output quantizes
    to e5m2 (wide range for gradient outliers), the saved activation and
    weight re-quantize to e4m3 — so ALL step matmul FLOPs run at fp8
    operand width.  ``impl``: "xla" or "pallas" (forward kernel;
    backward stays XLA).  ``amax_history_len``: > 0 selects delayed
    scaling (see :func:`quantize_fp8`)."""
    out, _ = _fp8_dense_fwd(x, w, impl, interpret, amax_history_len)
    return out


def _fp8_dense_fwd(x, w, impl, interpret, hist):
    lead = x.shape[:-1]
    K = x.shape[-1]
    x2 = x.reshape(-1, K)
    xq, xs = quantize_fp8(x2, FP8_FWD_DTYPE, amax_history_len=hist)
    wq, ws = quantize_fp8(w, FP8_FWD_DTYPE, amax_history_len=hist)
    if impl == "pallas":
        out = fp8_matmul_pallas(xq, xs, wq, ws, out_dtype=x.dtype,
                                interpret=interpret)
    else:
        out = fp8_matmul(xq, xs, wq, ws, (((1,), (0,)), ((), ())),
                         x.dtype)
    return out.reshape(*lead, w.shape[1]), (x, w)


def _fp8_dense_bwd(impl, interpret, hist, res, g):
    x, w = res
    lead = x.shape[:-1]
    K, N = w.shape
    g2 = g.reshape(-1, N)
    x2 = x.reshape(-1, K)
    gq, gs = quantize_fp8(g2, FP8_BWD_DTYPE, amax_history_len=hist)
    # dX = g · Wᵀ (contraction over N): e5m2 grad × e4m3 weight
    wq, ws = quantize_fp8(w, FP8_FWD_DTYPE, amax_history_len=hist)
    gx = fp8_matmul(gq, gs, wq, ws, (((1,), (1,)), ((), ())), x.dtype)
    # dW = Xᵀ · g (contraction over M): e4m3 activation × e5m2 grad
    xq, xs = quantize_fp8(x2, FP8_FWD_DTYPE, amax_history_len=hist)
    gw = fp8_matmul(xq, xs, gq, gs, (((0,), (0,)), ((), ())), w.dtype)
    return gx.reshape(*lead, K), gw


fp8_dense.defvjp(_fp8_dense_fwd, _fp8_dense_bwd)
