"""Paged-attention decode kernel (Pallas).

``serving/engine._paged_layer_body`` attends against the paged KV pool
by first gathering every slot's pages into a contiguous
``(B, V, n_kv, hd)`` HBM view (``pk[pages]``) and then contracting over
it.  That gather is pure data movement: for a decode step (S == 1) it
re-materializes the entire visible KV window per layer per token just
to feed one matvec-sized contraction.

This kernel reads the pages IN PLACE instead: the page table row rides
into the kernel, and each page is dynamically loaded from the pool ref
straight into kernel-local (VMEM-resident on TPU) storage — the
``(B, V, n_kv, hd)`` intermediate never exists at the XLA level, so HBM
traffic drops from (gather-write + gather-read) to a single pool read.
The attention math on the in-kernel view is the exact op sequence of
``_paged_layer_body`` — same einsum specs, mask constant, softmax axis,
probs cast, and (for int8 pools) the same quantize/scale-fold ordering
with the per-page scales folded in-kernel — so the kernel output equals
the reference path on matched inputs: bitwise for int8 pools (integer
accumulation), to float32 summation order for float pools (asserted in
tests/test_kernels.py on the CPU interpret tier).

CPU tier only.  The design does not lower on a TPU (:func:`refuse_on_tpu`
carries what Pallas said on a v5e): the whole pool is one block, the page
id is read from a vector-memory ref and the view is assembled with
``dynamic_update_slice``.  The hardware kernel keeps the page table in
SMEM (scalar prefetch) and DMAs pages — ROADMAP S3.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["paged_attention_decode", "refuse_on_tpu"]

# What Pallas' TPU lowering says to both serving kernels (jax 0.9.0,
# libtpu 0.0.34, TPU v5 lite; ``.lower(lowering_platforms=("tpu",))``
# reproduces it without a chip).
TPU_REFUSAL = (
    "\"The Pallas TPU lowering currently requires that the last two "
    "dimensions of your block shape are divisible by 8 and 128 "
    "respectively, or be equal to the respective dimensions of the overall "
    "array\" for the (1, P) page-table row; and with the table and "
    "positions passed whole, \"Unimplemented primitive in Pallas TPU "
    "lowering for KernelType.TC: dynamic_update_slice\" for the in-kernel "
    "KV view")


def refuse_on_tpu(kernel: str) -> None:
    """Raise on a TPU backend, whatever ``interpret`` says: the kernel
    neither compiles there nor may run interpreted or give way to the
    gather path behind the caller's back."""
    if jax.default_backend() == "tpu":
        raise NotImplementedError(
            f"{kernel} does not lower on a TPU — {TPU_REFUSAL}.  The "
            f"kernel is CPU-tier (interpret mode) until it is rewritten "
            f"for the hardware (ROADMAP S3); leave its flag off and the "
            f"engine serves through its XLA gather path.")


def _gather_pool(pool_ref, pages_ref, n_slot_pages: int, page: int):
    """Load this slot's pages from the pool ref into one kernel-local
    ``(V, …)`` array via dynamically-indexed page reads (no XLA-level
    gather)."""
    tail = pool_ref.shape[2:]
    acc0 = jnp.zeros((n_slot_pages * page,) + tail, pool_ref.dtype)

    def load(p, acc):
        blk = pool_ref[pages_ref[0, p]]
        return jax.lax.dynamic_update_slice(
            acc, blk, (p * page,) + (0,) * len(tail))

    return jax.lax.fori_loop(0, n_slot_pages, load, acc0)


def _decode_kernel(pages_ref, q_ref, apos_ref, pk_ref, pv_ref, o_ref, *,
                   n_slot_pages: int, probs_dtype):
    """Float pool: mirror of the non-quantized `_paged_layer_body`
    attention core for one batch slot (S == 1)."""
    page = pk_ref.shape[1]
    hd = q_ref.shape[-1]
    q = q_ref[0, 0]                                       # (g, r, hd)
    a = apos_ref[0, 0]
    kv = _gather_pool(pk_ref, pages_ref, n_slot_pages, page)   # (V, g, hd)
    vv = _gather_pool(pv_ref, pages_ref, n_slot_pages, page)
    scores = jnp.einsum("grh,kgh->grk", q, kv,
                        preferred_element_type=jnp.float32) / math.sqrt(hd)
    vis = jnp.arange(kv.shape[0]) <= a
    scores = jnp.where(vis[None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    o_ref[0, 0] = jnp.einsum("grk,kgh->grh", probs.astype(probs_dtype),
                             vv, preferred_element_type=jnp.float32)


def _decode_kernel_q8(pages_ref, q_ref, qs_ref, apos_ref, pk_ref, pv_ref,
                      pks_ref, pvs_ref, o_ref, *, n_slot_pages: int):
    """int8 pool: the quantized `_paged_layer_body` attention core with
    the per-page K/V scales folded in-kernel (scale-fold order matches
    the reference exactly for bitwise parity)."""
    from .quant import quantize_int8
    page = pk_ref.shape[1]
    hd = q_ref.shape[-1]
    qq = q_ref[0, 0]                                      # int8 (g, r, hd)
    qs = qs_ref[0, 0]                                     # f32  (g, r, 1)
    a = apos_ref[0, 0]
    kv = _gather_pool(pk_ref, pages_ref, n_slot_pages, page)   # int8 (V, g, hd)
    vv = _gather_pool(pv_ref, pages_ref, n_slot_pages, page)
    ks = _gather_pool(pks_ref, pages_ref, n_slot_pages, page)  # f32 (V, g, 1)
    vs = _gather_pool(pvs_ref, pages_ref, n_slot_pages, page)
    scores_i = jnp.einsum("grh,kgh->grk", qq, kv,
                          preferred_element_type=jnp.int32)
    scores = (scores_i.astype(jnp.float32) * qs
              * ks[..., 0].T[:, None, :]) / math.sqrt(hd)
    vis = jnp.arange(kv.shape[0]) <= a
    scores = jnp.where(vis[None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    pvw = probs * vs[..., 0].T[:, None, :]
    pvq, pv_sc = quantize_int8(pvw, axis=-1)
    attn_i = jnp.einsum("grk,kgh->grh", pvq, vv,
                        preferred_element_type=jnp.int32)
    o_ref[0, 0] = attn_i.astype(jnp.float32) * pv_sc


def paged_attention_decode(qg, pk, pv, pages, apos, *, q_scale=None,
                           pk_s=None, pv_s=None, probs_dtype=None,
                           interpret: bool = True):
    """Decode-step paged attention, pages read in place via the table.

    qg (B, 1, n_kv, rep, hd) — grouped query (already rope'd); int8
    codes with ``q_scale`` (B, 1, n_kv, rep, 1) f32 when the pool is
    int8.  pk/pv (n_pages, page, n_kv, hd); pk_s/pv_s their f32 scales
    for int8 pools.  pages (B, P) int32 page table; apos (B, 1) int32
    absolute position of the new row.  Returns f32 (B, 1, n_kv, rep,
    hd), the exact value of the reference gather-then-einsum path
    (caller applies the same ``astype`` epilogue).
    """
    import functools
    refuse_on_tpu("paged_attention_decode")
    B, S, nkv, rep, hd = qg.shape
    if S != 1:
        raise ValueError(f"decode kernel is S==1 only, got S={S}")
    P = pages.shape[1]
    page = pk.shape[1]
    quantized = pk.dtype == jnp.int8

    whole = lambda arr: pl.BlockSpec(
        arr.shape, lambda b: (0,) * arr.ndim)
    row = pl.BlockSpec((1, P), lambda b: (b, 0))
    qspec = pl.BlockSpec((1, 1, nkv, rep, hd), lambda b: (b, 0, 0, 0, 0))
    aspec = pl.BlockSpec((1, 1), lambda b: (b, 0))
    out_spec = pl.BlockSpec((1, 1, nkv, rep, hd), lambda b: (b, 0, 0, 0, 0))
    out_shape = jax.ShapeDtypeStruct((B, 1, nkv, rep, hd), jnp.float32)

    if quantized:
        if q_scale is None or pk_s is None or pv_s is None:
            raise ValueError("int8 pool needs q_scale, pk_s and pv_s")
        kernel = functools.partial(_decode_kernel_q8, n_slot_pages=P)
        sspec = pl.BlockSpec((1, 1, nkv, rep, 1), lambda b: (b, 0, 0, 0, 0))
        return pl.pallas_call(
            kernel,
            grid=(B,),
            in_specs=[row, qspec, sspec, aspec, whole(pk), whole(pv),
                      whole(pk_s), whole(pv_s)],
            out_specs=out_spec,
            out_shape=out_shape,
            interpret=interpret,
        )(pages, qg, q_scale, apos, pk, pv, pk_s, pv_s)

    kernel = functools.partial(
        _decode_kernel, n_slot_pages=P,
        probs_dtype=probs_dtype or qg.dtype)
    return pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=[row, qspec, aspec, whole(pk), whole(pv)],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(pages, qg, apos, pk, pv)
