"""Flash prefill kernel over the paged KV pool (Pallas).

The serving engine's prefill attends a whole chunk of S query rows
against the slot's visible KV window.  The reference path gathers the
page table into a contiguous ``(B, V, n_kv, hd)`` HBM view and runs two
einsums with a full ``(g, r, S, V)`` score tensor in between — fine at
toy scale, but the score tensor and the gather view are exactly the
materializations a fused flash kernel exists to avoid.

This kernel reads pages IN PLACE via the table (same dynamic page loads
as ``paged_attention.py``) and computes the chunk's attention with a
tiled ONLINE softmax: KV is consumed in blocks of ``kv_block_pages``
pages, carrying running per-row maxima ``m``, denominators ``l`` and a
rescaled accumulator — the classic divide-at-the-end flash recurrence,
so the full score tensor never exists at once.

Parity tiers:

  * ``kv_block_pages=None`` (default) — ONE tile covering the whole
    view.  The epilogue then follows the reference op order exactly
    (mask → ``jax.nn.softmax`` → probs cast → contraction), which makes
    the output equal to the engine's gather+einsum path up to float32
    summation order — the tier the serving parity gates run.
  * ``kv_block_pages=k`` — genuine multi-block online softmax.  The
    divide-at-end rescaling reassociates the denominator, so this tier
    is allclose-not-bitwise vs the reference (asserted in tests); it is
    the shape the hardware tier runs where VMEM can't hold the view.

Float pools only: the int8 pool's per-row scale folding does not
commute with the online rescale, and prefill is the bandwidth-bound
leg where bf16 pools are the default anyway.

CPU tier only, still fenced (``paged_attention.refuse_on_tpu``): the
design shares the page loads of ``paged_attention.py``'s int8 kernel
(``_gather_pool``: the page id read from a vector-memory ref, the view
assembled with ``dynamic_update_slice``, the whole pool one block) and
does not lower on a TPU.  The float DECODE kernel there was rewritten
for the hardware (scalar-prefetched table, per-page DMAs); this kernel
is the next one to follow it (ROADMAP S3), and until then prefill on
the chip is the gather path.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .paged_attention import _gather_pool, refuse_on_tpu

__all__ = ["paged_flash_prefill"]


def _prefill_kernel(pages_ref, q_ref, apos_ref, pk_ref, pv_ref, o_ref, *,
                    n_slot_pages: int, kv_block_pages: int | None,
                    probs_dtype):
    """One batch slot's chunk attention: q (S, g, r, hd) against the
    slot's pages, causal on absolute positions (``pos_kv <= apos[s]``,
    masked positions scored −1e30 → exact-zero probability)."""
    page = pk_ref.shape[1]
    hd = q_ref.shape[-1]
    q = q_ref[0]                                     # (S, g, r, hd)
    a = apos_ref[0]                                  # (S,)

    if kv_block_pages is None:
        # single tile: the reference op order verbatim (softmax →
        # probs cast → contraction) — bitwise tier
        kv = _gather_pool(pk_ref, pages_ref, n_slot_pages, page)
        vv = _gather_pool(pv_ref, pages_ref, n_slot_pages, page)
        scores = jnp.einsum(
            "sgrh,kgh->grsk", q, kv,
            preferred_element_type=jnp.float32) / math.sqrt(hd)
        vis = jnp.arange(kv.shape[0])[None, :] <= a[:, None]  # (S, V)
        scores = jnp.where(vis[None, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        o_ref[0] = jnp.einsum("grsk,kgh->sgrh",
                              probs.astype(probs_dtype), vv,
                              preferred_element_type=jnp.float32)
        return

    # tiled online softmax: running (m, l, acc), divide at the end
    T = kv_block_pages * page
    S, g, r, _ = q.shape

    def gather_blk(pool_ref, i):
        tail = pool_ref.shape[2:]
        acc0 = jnp.zeros((T,) + tail, pool_ref.dtype)

        def load(p, accv):
            blk = pool_ref[pages_ref[0, i * kv_block_pages + p]]
            return jax.lax.dynamic_update_slice(
                accv, blk, (p * page,) + (0,) * len(tail))

        return jax.lax.fori_loop(0, kv_block_pages, load, acc0)

    def block(i, carry):
        m, l, acc = carry
        kb = gather_blk(pk_ref, i)
        vb = gather_blk(pv_ref, i)
        s = jnp.einsum(
            "sgrh,kgh->grsk", q, kb,
            preferred_element_type=jnp.float32) / math.sqrt(hd)
        pos = i * T + jnp.arange(T)
        vis = pos[None, :] <= a[:, None]             # (S, T)
        s = jnp.where(vis[None, None], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))  # (g, r, S)
        # block 0 always holds position 0, visible to every row, so
        # m_new is a real score from the first iteration on and the
        # −1e30 of fully-masked later blocks underflows to exactly 0
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = l * corr + jnp.sum(p, axis=-1)
        pv_blk = jnp.einsum("grsk,kgh->sgrh", p.astype(probs_dtype),
                            vb, preferred_element_type=jnp.float32)
        acc = acc * corr.transpose(2, 0, 1)[..., None] + pv_blk
        return m_new, l, acc

    m0 = jnp.full((g, r, S), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((g, r, S), jnp.float32)
    a0 = jnp.zeros((S, g, r, hd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_slot_pages // kv_block_pages,
                                  block, (m0, l0, a0))
    o_ref[0] = acc / l.transpose(2, 0, 1)[..., None]


def paged_flash_prefill(qg, pk, pv, pages, apos, *, probs_dtype=None,
                        kv_block_pages: int | None = None,
                        interpret: bool = True):
    """Chunked-prefill paged flash attention, pages read in place.

    qg (B, S, n_kv, rep, hd) grouped query (already rope'd); pk/pv
    (n_pages, page, n_kv, hd) float pools; pages (B, P) int32 page
    table; apos (B, S) int32 absolute positions of the chunk's rows.
    Returns f32 (B, S, n_kv, rep, hd) — with the default single tile,
    the exact value of the reference gather-then-einsum path (caller
    applies the same ``astype`` epilogue).  ``kv_block_pages`` must
    divide P; passing P is the same as None.
    """
    refuse_on_tpu("paged_flash_prefill")
    if pk.dtype == jnp.int8:
        raise ValueError("flash prefill is float-pool only (int8 "
                         "scale folding does not commute with the "
                         "online rescale)")
    B, S, nkv, rep, hd = qg.shape
    P = pages.shape[1]
    if kv_block_pages is not None:
        kv_block_pages = int(kv_block_pages)
        if not 0 < kv_block_pages <= P:
            raise ValueError(f"kv_block_pages={kv_block_pages} with "
                             f"{P} pages per slot")
        if P % kv_block_pages:
            raise ValueError(f"kv_block_pages={kv_block_pages} must "
                             f"divide the {P}-page table")
        if kv_block_pages == P:
            kv_block_pages = None          # degenerate → bitwise tier

    kernel = functools.partial(
        _prefill_kernel, n_slot_pages=P,
        kv_block_pages=kv_block_pages,
        probs_dtype=probs_dtype or qg.dtype)
    whole = lambda arr: pl.BlockSpec(arr.shape, lambda b: (0,) * arr.ndim)
    row = pl.BlockSpec((1, P), lambda b: (b, 0))
    qspec = pl.BlockSpec((1, S, nkv, rep, hd), lambda b: (b, 0, 0, 0, 0))
    aspec = pl.BlockSpec((1, S), lambda b: (b, 0))
    out_spec = pl.BlockSpec((1, S, nkv, rep, hd),
                            lambda b: (b, 0, 0, 0, 0))
    out_shape = jax.ShapeDtypeStruct((B, S, nkv, rep, hd), jnp.float32)
    return pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=[row, qspec, aspec, whole(pk), whole(pv)],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(pages, qg, apos, pk, pv)
