"""Paged-attention decode kernels (Pallas).

``serving/engine._paged_attend`` attends against the paged KV pool
by first gathering every slot's pages into a contiguous
``(B, V, n_kv, hd)`` HBM view (``pk[pages]``) and then contracting over
it.  That gather is pure data movement: for a decode step (S == 1) it
re-materializes the entire visible KV window per layer per token just
to feed one matvec-sized contraction, whatever the slots hold.

The kernels here read the pages IN PLACE instead; the
``(B, V, n_kv, hd)`` intermediate never exists at the XLA level.

**Float pools: the hardware kernel** (:func:`_decode_kernel`, the
engine's default decode path on a TPU; the design of
``jax.experimental.pallas.ops.tpu.paged_attention``, adapted to this
repo's page-major pool).  The page table and the per-slot lengths ride
in by scalar prefetch (SMEM); the pools stay in HBM; one grid step per
slot multiplies that slot's pages, which async DMAs bring to VMEM in
blocks of ``PAGES_PER_BLOCK`` pages, and runs an online softmax over the
blocks.  The copy schedule (:func:`pages_copied` states it):

* of a block, only the pages that hold a position the slot sees are
  copied, i.e. table entries ``lo // page_size`` up to the one that holds
  the slot's last position: no page past a slot's length, and for a
  window layer none wholly under its lower bound.  The rows of the
  buffer behind a page that is not copied keep what they held; their
  columns are masked, and the V halves are zeroed once a call so that
  what they held is never a NaN;
* the copies run ahead of the products ACROSS grid steps: while a block
  is multiplied the next one is in flight into the other half of the
  double buffer, and after a slot's last block that is the first block
  of the next slot that reads anything (slots of length 0 are skipped
  over: they start nothing, wait for nothing and emit zeros).  Only the
  call's first block is waited for with nothing else to do.  Which half
  is in flight is carried from grid step to grid step in SMEM scratch,
  as the library's kernel does.

One page of the pool is one contiguous
``(page_size * n_kv, hd)`` slab that carries every KV head, so it is one
copy; the kernel contracts the slot's ``n_kv * rep`` query rows against
ALL the slab's rows on the MXU and masks the columns of the other KV
heads together with the positions past the length (no strided access,
every operand natively tiled; the MXU streams the same K and V rows
either way).  Scores are float32 from the pool-dtype operands,
probabilities are cast to ``probs_dtype`` before the ``p @ V`` product,
which accumulates in float32: the gather path's precision.  The online
softmax changes the summation order, so the output equals the gather
path to float32 summation order, not bitwise (tests/test_kernels.py,
interpret mode on the CPU; chip_smoke.py on the chip).
:func:`decode_kernel_takes` states which shapes it takes.

**int8 pools: CPU tier only** (:func:`_decode_kernel_q8`; bitwise equal
to the quantized gather path, integer accumulation).  Its design does
not lower on a TPU (:func:`refuse_on_tpu` carries what Pallas said on a
v5e): the whole pool is one block, the page id is read from a
vector-memory ref and the view is assembled with
``dynamic_update_slice``.  ``ops/flash_prefill.py`` shared that design
and that fence until PR 27 rewrote it after the float kernel here.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_attention_decode", "paged_latent_attention_decode",
           "decode_kernel_takes", "pages_copied", "refuse_on_tpu"]

# What Pallas' TPU lowering says to the int8 decode kernel, and said to
# the flash-prefill kernel before PR 27 (jax 0.9.0, libtpu 0.0.34, TPU v5
# lite; ``.lower(lowering_platforms=("tpu",))`` reproduces it, chip or not).
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
            f"for the hardware as the float kernels were; leave its flag "
            f"off and the engine serves through its XLA gather path.")


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


# Pages per DMA block of the kernels: 16 pages of 16 tokens are 256
# positions a block, 1,024 score columns with 4 KV heads, 1 MB of VMEM
# for both pools' double buffers.  The block is what one product
# multiplies and what the double buffer holds, no longer what is copied:
# the float kernel copies a block's live pages alone (the latent kernel
# still copies it whole).  16 was chosen on a v5e under the float kernel's
# OLD schedule (whole blocks, a wait at every slot's head: 4 and 8 slower
# everywhere, 32 slower for chat's ~300-token slots and faster only past
# ~4k tokens a slot; PERF.md §6, PR 24) and has not been swept again
# under this one (PERF.md §7, PR 51).  Tables shorter than that are one
# block.
PAGES_PER_BLOCK = 16


def pages_copied(length, page_size: int, lo=0):
    """The float decode kernel's copy schedule as a count: how many pages
    of each pool (K and V alike) the kernel copies for a slot that sees
    positions ``lo <= s < length``: its table entries from
    ``lo // page_size`` up to and including the one that holds position
    ``length - 1``, each once; 0 for a slot of length 0.  Pure, numpy,
    broadcasting over ``length`` and ``lo``: the engine counts with it
    (``stats["paged_pages_copied"]``) and tests/test_kernels.py holds the
    kernel to it."""
    length, lo = np.asarray(length), np.maximum(np.asarray(lo), 0)
    return np.maximum(-(-length // page_size) - lo // page_size, 0)


def decode_kernel_takes(dtype, head_dim: int, page_size: int) -> bool:
    """The shapes :func:`_decode_kernel` compiles for on a TPU: a float
    pool whose page is whole native tiles in VMEM, i.e. ``head_dim`` a
    multiple of the 128 lanes and ``page_size`` a multiple of the
    dtype's sublane tile (8 rows of 32 bits: 8 for float32, 16 for
    bfloat16).  Interpret mode takes any float shape."""
    dtype = jnp.dtype(dtype)
    if not jnp.issubdtype(dtype, jnp.floating) or dtype.itemsize > 4:
        return False
    return head_dim % 128 == 0 and page_size % (32 // dtype.itemsize) == 0


def _decode_kernel(len_ref, pages_ref, *refs, table_pages: int,
                   block_pages: int, page: int, rep: int, probs_dtype,
                   bounded: bool = False, scale: float | None = None):
    """Float pool, one batch slot (S == 1) a grid step.  ``len_ref`` (B,)
    and ``pages_ref`` (B * P,) are in SMEM; q_ref (1, R, hd) holds the
    slot's R = n_kv * rep query rows; pk_hbm/pv_hbm are the pools as
    (n_pages, page * n_kv, hd), left in HBM; col_ref (2, T) says of each
    of a block's T = block_pages * page * n_kv columns its position
    within the block and its KV head; k_buf/v_buf (2, T, hd) are the two
    halves of the double buffer; sems (2, 2) their K and V semaphores;
    ``flight`` (2,) in SMEM carries the copy schedule from one grid step
    to the next: the half that the next block to be multiplied is copied
    into, and whether any block has been started yet.
    ``bounded``: one more scalar-prefetched operand leads ``refs``,
    ``lo_ref`` (B,), the first position a slot sees (a window layer's):
    blocks and pages wholly under it are not copied, positions under it
    in its page are masked."""
    lo_ref = refs[0] if bounded else None
    q_ref, col_ref, pk_hbm, pv_hbm, o_ref, k_buf, v_buf, sems, flight = \
        refs[int(bounded):]
    b, n_slots = pl.program_id(0), pl.num_programs(0)
    rows = k_buf.shape[1] // block_pages       # pool rows a page
    hd = q_ref.shape[-1]
    span = block_pages * page                  # positions a block

    def div(x, d: int):
        """``x // d`` of a scalar ``x >= 0``: a shift where ``d`` is a power
        of two (the scalar core divides in many cycles, and this runs
        between a block's copies and its product)."""
        return x >> (d.bit_length() - 1) if d & (d - 1) == 0 else x // d

    def live_pages(s):
        """The table entries [first, end) of slot ``s`` that hold a
        position it sees: what :func:`pages_copied` counts."""
        end = jnp.minimum(div(len_ref[s] + page - 1, page), table_pages)
        first = div(jnp.maximum(lo_ref[s], 0), page) if bounded else 0
        return first, end

    def live_blocks(s):
        """The blocks [first, end) that hold those pages; none: end <=
        first."""
        first, end = live_pages(s)
        return (div(first, block_pages) if bounded else 0,
                div(end + block_pages - 1, block_pages))

    def copies(s, blk, half, go):
        """``go`` (start or wait) the copy of each page of slot ``s``'s
        block ``blk`` that holds a position the slot sees, into ``half``;
        the rows behind the others keep what the half held.  A block
        whose every page is seen (all but a long slot's last) takes one
        branch, not one a page."""
        first, end = live_pages(s)
        base = blk * block_pages

        def page(i):
            pid = pages_ref[s * table_pages + base + i]
            dst = pl.ds(i * rows, rows)
            go(pltpu.make_async_copy(
                pk_hbm.at[pid], k_buf.at[half, dst], sems.at[0, half]))
            go(pltpu.make_async_copy(
                pv_hbm.at[pid], v_buf.at[half, dst], sems.at[1, half]))

        whole = base + block_pages <= end
        if bounded:
            whole = jnp.logical_and(whole, base >= first)

        @pl.when(whole)
        def _():
            for i in range(block_pages):
                page(i)

        @pl.when(jnp.logical_not(whole))
        def _():
            for i in range(block_pages):
                seen = base + i < end
                if bounded:
                    seen = jnp.logical_and(seen, base + i >= first)
                pl.when(seen)(functools.partial(page, i))

    start = lambda c: c.start()
    wait = lambda c: c.wait()
    length = len_ref[b]
    lo = jnp.maximum(lo_ref[b], 0) if bounded else None
    blk0, n_blocks = live_blocks(b)

    @pl.when(b == 0)
    def _():
        flight[0] = 0
        flight[1] = 0
        # a page that is not copied leaves its rows of the half as they
        # were: other slots' rows later, but whatever the memory held in
        # a call's first blocks, and a probability of 0.0 times a NaN is
        # a NaN in ``p @ V``.  (K needs none: its scores are replaced,
        # not multiplied, where a column is masked.)
        v_buf[...] = jnp.zeros_like(v_buf)

    half0 = flight[0]

    # the call's first block that reads anything starts its own copies;
    # every later one was started while its predecessor was multiplied
    @pl.when(jnp.logical_and(n_blocks > blk0, flight[1] == 0))
    def _():
        copies(b, blk0, half0, start)

    def after(blk):
        """The (slot, block) multiplied after this slot's ``blk``: its
        next block, or the first block of the next slot that reads
        anything; slot ``n_slots`` when there is none."""
        def reads_nothing(s):
            first, end = live_blocks(jnp.minimum(s, n_slots - 1))
            return jnp.logical_and(s < n_slots, end <= first)

        def next_slot():
            s = jax.lax.while_loop(reads_nothing, lambda s: s + 1, b + 1)
            return s, live_blocks(jnp.minimum(s, n_slots - 1))[0]
        return jax.lax.cond(blk + 1 < n_blocks,
                            lambda: (b, blk + 1), next_slot)

    q = q_ref[0]                                              # (R, hd)
    R = q.shape[0]
    row_head = jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0) // rep
    own_head = col_ref[1:2, :] == row_head                    # (R, T)
    col_pos = col_ref[0:1, :]                                 # (1, T)

    def block(blk, carry):
        m, l, acc = carry
        half = (half0 + blk - blk0) & 1
        nxt_slot, nxt_blk = after(blk)

        @pl.when(nxt_slot < n_slots)
        def _():
            copies(nxt_slot, nxt_blk, 1 - half, start)

        copies(b, blk, half, wait)
        s = jax.lax.dot_general(
            q, k_buf[half], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        s = s / math.sqrt(hd) if scale is None else s * scale
        vis = jnp.logical_and(own_head, blk * span + col_pos < length)
        if bounded:
            vis = jnp.logical_and(vis, blk * span + col_pos >= lo)
        s = jnp.where(vis, s, -1e30)
        # every block the loop runs holds a position the slot sees, so
        # each row sees a real score in it and the -1e30 of a masked
        # column underflows to exactly 0
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jnp.dot(p.astype(probs_dtype), v_buf[half],
                                   preferred_element_type=jnp.float32)
        return m_new, l, acc

    m0 = jnp.full((R, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((R, 1), jnp.float32)
    a0 = jnp.zeros((R, hd), jnp.float32)
    _, l, acc = jax.lax.fori_loop(blk0, n_blocks, block, (m0, l0, a0))
    o_ref[0] = acc / jnp.where(l == 0.0, 1.0, l)

    @pl.when(n_blocks > blk0)
    def _():
        flight[0] = (half0 + n_blocks - blk0) & 1
        flight[1] = 1


def _decode_kernel_q8(pages_ref, q_ref, qs_ref, apos_ref, pk_ref, pv_ref,
                      pks_ref, pvs_ref, o_ref, *, n_slot_pages: int):
    """int8 pool: the quantized `_paged_attend` attention core with
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


def paged_attention_decode(qg, pk, pv, pages, apos, *, valid=None,
                           q_scale=None, pk_s=None, pv_s=None, lo=None,
                           scale: float | None = None,
                           probs_dtype=None, interpret: bool | None = None):
    """Decode-step paged attention, pages read in place via the table.

    qg (B, 1, n_kv, rep, hd) — grouped query (already rope'd); int8
    codes with ``q_scale`` (B, 1, n_kv, rep, 1) f32 when the pool is
    int8.  pk/pv (n_pages, page, n_kv, hd); pk_s/pv_s their f32 scales
    for int8 pools.  pages (B, P) int32 page table; apos (B, 1) int32
    absolute position of the new row; valid (B, 1) bool, False for a
    slot that holds no request (float pools: it reads no page and gets
    zeros; default all True); lo (B, 1) int32, float pools only: the first
    position the row sees (a window layer's lower bound; default: 0,
    and the program is the one without the operand); ``scale``, float
    pools only: what the scores are multiplied by (default: divided by
    ``sqrt(hd)``).  Returns f32 (B, 1, n_kv, rep, hd), the
    value of the reference gather-then-einsum path: exactly for int8
    pools, to float32 summation order for float pools (caller applies
    the same ``astype`` epilogue).  ``interpret`` None: compiled on a
    TPU, interpreted elsewhere.
    """
    B, S, nkv, rep, hd = qg.shape
    if S != 1:
        raise ValueError(f"decode kernel is S==1 only, got S={S}")
    P = pages.shape[1]
    n_pages, page = pk.shape[:2]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    if pk.dtype == jnp.int8:
        refuse_on_tpu("paged_attention_decode (int8 pool)")
        if q_scale is None or pk_s is None or pv_s is None:
            raise ValueError("int8 pool needs q_scale, pk_s and pv_s")
        if lo is not None or scale is not None:
            raise ValueError("the int8 decode kernel takes no lower bound "
                             "and no scale of its own")
        whole = lambda arr: pl.BlockSpec(
            arr.shape, lambda b: (0,) * arr.ndim)
        wide = lambda last: pl.BlockSpec(
            (1, 1, nkv, rep, last), lambda b: (b, 0, 0, 0, 0))
        return pl.pallas_call(
            functools.partial(_decode_kernel_q8, n_slot_pages=P),
            grid=(B,),
            in_specs=[pl.BlockSpec((1, P), lambda b: (b, 0)), wide(hd),
                      wide(1), pl.BlockSpec((1, 1), lambda b: (b, 0)),
                      whole(pk), whole(pv), whole(pk_s), whole(pv_s)],
            out_specs=wide(hd),
            out_shape=jax.ShapeDtypeStruct((B, 1, nkv, rep, hd),
                                           jnp.float32),
            interpret=interpret,
        )(pages, qg, q_scale, apos, pk, pv, pk_s, pv_s)

    if not interpret and not decode_kernel_takes(pk.dtype, hd, page):
        raise ValueError(
            f"the paged decode kernel does not compile for a {pk.dtype} "
            f"pool with head_dim {hd} and page_size {page} "
            f"(decode_kernel_takes); the engine's gather path serves it")
    lengths = apos[:, 0] + 1
    if valid is not None:
        lengths = jnp.where(valid[:, 0], lengths, 0)
    bound = () if lo is None else (lo[:, 0],)
    return _decode_float(
        qg, pk, pv, pages, lengths, *bound,
        block_pages=min(PAGES_PER_BLOCK, P),
        probs_dtype=jnp.dtype(probs_dtype or qg.dtype),
        interpret=bool(interpret),
        **({} if scale is None else {"scale": float(scale)}))


# jitted so that the layers of one decode program, which call it with the
# same shapes, share one trace and one Mosaic lowering: unshared, 36
# layers added ~10 s to every engine's warm-up, compile cache or not
@functools.partial(jax.jit, static_argnames=("block_pages", "probs_dtype",
                                             "interpret", "scale"))
def _decode_float(qg, pk, pv, pages, lengths, lo=None, *, block_pages: int,
                  probs_dtype, interpret: bool, scale: float | None = None):
    """The float kernel's call: qg (B, 1, n_kv, rep, hd), the pools as
    the engine holds them, pages (B, P), lengths (B,) with 0 for a slot
    that reads nothing, and where given lo (B,), the first position each
    slot sees."""
    B, _, nkv, rep, hd = qg.shape
    P = pages.shape[1]
    n_pages, page = pk.shape[:2]
    R, rows = nkv * rep, page * nkv
    T = block_pages * rows
    # row w of a page's (page * n_kv, hd) slab is token w // n_kv of the
    # page, KV head w % n_kv (numpy: a literal of the program, not ops)
    w = np.arange(T, dtype=np.int32) % rows
    cols = np.stack([(np.arange(T, dtype=np.int32) // rows) * page
                     + w // nkv, w % nkv])
    bound = () if lo is None else (lo.astype(jnp.int32),)
    kernel = functools.partial(
        _decode_kernel, table_pages=P, block_pages=block_pages, page=page,
        rep=rep, probs_dtype=probs_dtype, bounded=bool(bound), scale=scale)
    slot = pl.BlockSpec((1, R, hd), lambda b, *_: (b, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2 + len(bound),
            grid=(B,),
            in_specs=[slot, pl.BlockSpec((2, T), lambda b, *_: (0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=slot,
            scratch_shapes=[pltpu.VMEM((2, T, hd), pk.dtype),
                            pltpu.VMEM((2, T, hd), pv.dtype),
                            pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.SMEM((2,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((B, R, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(lengths.astype(jnp.int32), pages.reshape(-1).astype(jnp.int32),
      *bound, qg.reshape(B, R, hd), cols, pk.reshape(n_pages, rows, hd),
      pv.reshape(n_pages, rows, hd))
    return out.reshape(B, 1, nkv, rep, hd)


# ------------------------------------------------------------ latent pools
#
# Multi-head latent attention (``models/mla_moe.py``): the float kernel's
# design for a pool that holds ONE row ``[c_kv | k_rope]`` a token and no V
# pool.  The slot's query rows are its heads' ABSORBED queries
# ``[q~ | q_rope]``, every head reads the same rows (one "KV head", so no
# column is another head's), and a block's first ``rank`` columns serve a
# second time as the values: the output is ``o~``, the probabilities' sum of
# latents, which the caller up-projects.  The cached rows never are.  (This
# section stands at the end of the file so that the float kernel above keeps
# its line numbers, which Mosaic serializes into the dense programs.)

def _latent_decode_kernel(len_ref, pages_ref, q_ref, rows_hbm, o_ref, buf,
                          sems, *, table_pages: int, block_pages: int,
                          page: int, rank: int, scale: float, probs_dtype):
    """Latent pool, one batch slot (S == 1).  ``len_ref`` (B,) and
    ``pages_ref`` (B * P,) are in SMEM; q_ref (1, n, W) holds the slot's
    absorbed queries ``[q~ | q_rope]``, W = rank + rope; rows_hbm is the
    pool as (n_pages, page, W), left in HBM; buf (2, T, W) the double
    buffer of T = block_pages * page cached rows, sems (2,) its
    semaphores.  Writes ``o~`` (1, n, rank), float32."""
    b = pl.program_id(0)
    length = len_ref[b]
    span = block_pages * page                  # positions a block
    n_blocks = (length + span - 1) // span

    def copies(blk, slot):
        out = []
        for i in range(block_pages):
            # a table that is no multiple of the block ends in repeats of
            # its last entry; their positions are past every length
            at = jnp.minimum(blk * block_pages + i, table_pages - 1)
            pid = pages_ref[b * table_pages + at]
            out.append(pltpu.make_async_copy(
                rows_hbm.at[pid], buf.at[slot, pl.ds(i * page, page)],
                sems.at[slot]))
        return out

    @pl.when(n_blocks > 0)
    def _():
        for c in copies(0, 0):
            c.start()

    q = q_ref[0]                                              # (n, W)
    n = q.shape[0]
    col_pos = jax.lax.broadcasted_iota(jnp.int32, (1, span), 1)

    def block(blk, carry):
        m, l, acc = carry
        slot = blk % 2

        @pl.when(blk + 1 < n_blocks)
        def _():
            for c in copies(blk + 1, 1 - slot):
                c.start()

        for c in copies(blk, slot):
            c.wait()
        rows = buf[slot]                                      # (T, W)
        s = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = jnp.where(blk * span + col_pos < length, s, -1e30)
        # every block the loop runs starts under the length, so each row
        # sees a real score in it and a masked column's exp is exactly 0
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jnp.dot(p.astype(probs_dtype), rows[:, :rank],
                                   preferred_element_type=jnp.float32)
        return m_new, l, acc

    m0 = jnp.full((n, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((n, 1), jnp.float32)
    a0 = jnp.zeros((n, rank), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, n_blocks, block, (m0, l0, a0))
    o_ref[0] = acc / jnp.where(l == 0.0, 1.0, l)


def paged_latent_attention_decode(qa, pool, pages, lengths, *, rank: int,
                                  scale: float, probs_dtype=None,
                                  interpret: bool | None = None):
    """Decode-step latent attention in absorbed form, the cached rows read
    in place via the table and never up-projected.

    qa (B, n, rank + rope): each head's absorbed query ``[q~ | q_rope]``;
    pool (n_pages, page, W): one row ``[c_kv | k_rope | 0...]`` a token,
    padded with zero columns to W, whole 128-lane tiles (a v5e holds a
    576-wide bf16 array in 640 columns anyway, and a page's copy must be
    whole tiles); pages (B, P) int32; lengths (B,) int32, the positions a
    slot may see (its new row among them), 0 for a slot that holds no
    request: it reads no page and gets zeros.  Scores are
    ``scale * qa . row`` in float32, probabilities are cast to
    ``probs_dtype`` (default: the pool's) before they sum the rows' first
    ``rank`` columns in float32.  Returns ``o~`` (B, n, rank) float32:
    plain absorbed attention over the gathered rows, to float32 summation
    order (``tests/test_mla_moe.py`` holds that oracle).  ``interpret``
    None: compiled on a TPU, interpreted elsewhere."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    page, W = pool.shape[1:]
    if qa.shape[-1] < W:     # the pool's rows end in zero columns: so do q's
        qa = jnp.pad(qa, ((0, 0), (0, 0), (0, W - qa.shape[-1])))
    if not interpret and not (decode_kernel_takes(pool.dtype, rank, page)
                              and W % 128 == 0):
        raise ValueError(
            f"the latent decode kernel does not compile for a {pool.dtype} "
            f"pool of rank {rank}, row width {W} and page_size {page} "
            f"(decode_kernel_takes, rows of whole 128-lane tiles); the "
            f"engine's XLA path serves it")
    return _decode_latent(
        qa, pool, pages, lengths, rank=int(rank), scale=float(scale),
        block_pages=min(PAGES_PER_BLOCK, pages.shape[1]),
        probs_dtype=jnp.dtype(probs_dtype or pool.dtype),
        interpret=bool(interpret))


# jitted for the reason ``_decode_float`` is: one trace a program
@functools.partial(jax.jit, static_argnames=(
    "rank", "scale", "block_pages", "probs_dtype", "interpret"))
def _decode_latent(qa, pool, pages, lengths, *, rank: int, scale: float,
                   block_pages: int, probs_dtype, interpret: bool):
    B, n, W = qa.shape
    P = pages.shape[1]
    page = pool.shape[1]
    kernel = functools.partial(
        _latent_decode_kernel, table_pages=P, block_pages=block_pages,
        page=page, rank=rank, scale=scale, probs_dtype=probs_dtype)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[pl.BlockSpec((1, n, W), lambda b, *_: (b, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, n, rank), lambda b, *_: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, block_pages * page, W),
                                       pool.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((B, n, rank), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(lengths.astype(jnp.int32), pages.reshape(-1).astype(jnp.int32),
      qa.astype(pool.dtype), pool)
