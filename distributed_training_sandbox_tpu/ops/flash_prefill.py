"""Flash prefill kernel over the paged KV pool (Pallas).

The serving engine's prefill attends a whole chunk of S query rows
against the slot's visible KV window.  The reference path
(``serving/engine._paged_attend``) gathers the slot's WHOLE page
table into a contiguous ``(B, V, n_kv, hd)`` HBM view and runs two
einsums with a full float32 ``(B, n_kv, rep, S, V)`` score tensor in
between, whatever part of the view the chunk can see.

This kernel reads the slot's live pages IN PLACE and keeps the scores in
VMEM: neither of those two tensors exists in the program.  It is the
design of ``paged_attention._decode_kernel`` at S > 1 (the engine's
default prefill path on a TPU since PR 27): the page table and, per
batch row, the chunk's start and its live end ride in by scalar prefetch
(SMEM); the pools stay in HBM in the layout they have; one grid step per
batch row copies that row's pages to VMEM by async DMA in
double-buffered blocks of ``PAGES_PER_BLOCK`` pages and runs an online
softmax over the blocks, in a loop that ENDS AT THE CHUNK'S LAST VISIBLE
POSITION and not at the table's end.  A row with nothing live reads no
page and returns zeros.

What differs from the decode kernel is how the KV heads are told apart.
A page of the pool is one ``(page_size * n_kv, hd)`` slab whose rows
interleave the heads.  At S == 1 the decode kernel contracts every query
row against every slab row and masks the other heads' columns, which is
free when one row pays a weight load; at S = 512 it would be ``n_kv``
times the MXU and the softmax work.  Here each KV head's ``S * rep``
query rows meet only that head's keys: a block's slab is widened to
float32 in VMEM once (packed bf16 rows cannot be read with a sublane
stride, 32-bit rows can) and head ``g`` is the strided read
``pl.ds(g, span, stride=n_kv)`` of it, cast back (exactly) to the pool's
dtype for the MXU.

The scores are held transposed, keys on sublanes and query rows on
lanes, so that the softmax's running maximum and sum are lane-dense
``(1, S * rep)`` rows reduced along sublanes (elementwise across
vregs), not ``(S * rep, 1)`` columns reduced across lanes.

Arithmetic: scores in float32 from pool-dtype operands, ``/ sqrt(hd)``,
causal on absolute positions (``pos_kv <= apos``, masked = −1e30),
online softmax across blocks in float32, probabilities cast to
``probs_dtype`` before ``p @ V``, float32 accumulation, one divide at
the end: the gather path's precision, reassociated only in float32
summation order (tests/test_kernels.py, interpret mode on the CPU;
chip_smoke.py on the chip).  The chunk's own K/V rows are in the pool
when the kernel runs (the scatter precedes it by data dependence).

Float pools only: the int8 pool's per-row scale folding does not commute
with the online rescale.  :func:`prefill_kernel_takes` states which
shapes compile on a TPU; interpret mode takes any float shape.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import decode_kernel_takes

__all__ = ["paged_flash_prefill", "prefill_kernel_takes"]

# Pages per DMA block: 8 pages of 16 tokens are 128 positions a block, a
# (128, S * rep) float32 score tile a KV head.  On a v5e at the serving
# cells' shapes (PERF.md §6, PR 27) 8 and 16 read alike at long live
# ends (0.099 | 0.100 ms a layer at 2,560 positions, 0.272 | 0.276 at
# 8,192), 8 is faster at short ones (a block is read whole: 0.032 |
# 0.042 at 300) and compiles in half the time, 4 is 30% slower
# everywhere and 32 no faster.  Tables shorter than a block are one.
PAGES_PER_BLOCK = 8

# VMEM the kernel may use: the query and output blocks of all heads,
# double-buffered by the pipeline, the accumulators and a block's score
# tiles are ~20 MB at the document cell's shapes, past the compiler's
# 16 MB default (a v5e core has 128 MB)
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def prefill_kernel_takes(dtype, head_dim: int, page_size: int,
                         chunk: int) -> bool:
    """The shapes :func:`_prefill_kernel` compiles for on a TPU: what
    the decode kernel takes (a float pool, ``head_dim`` whole 128-lane
    tiles, ``page_size`` whole sublane tiles of the dtype), and a chunk
    of whole sublane tiles too (8 rows of 32 bits: 8 for float32, 16 for
    bfloat16), so that a head's ``chunk * rep`` query rows are.
    Interpret mode takes any float shape."""
    return (decode_kernel_takes(dtype, head_dim, page_size)
            and chunk > 1
            and chunk % (32 // jnp.dtype(dtype).itemsize) == 0)


# ------------------------------------------------- heads wider than a tile

def _staging(T: int, hd: int):
    """The float32 staging of one block's slab, from which a KV head's rows
    are read with a sublane stride: ``(T, hd)`` at a head dim of one lane
    tile; at a wider one ``(hd / 128, T, 128)``, a plane a lane tile,
    because Mosaic reads with a stride only from a buffer whose rows are
    128 lanes ("The last dim size is not 128 in original base memref")."""
    wide = hd > 128 and hd % 128 == 0   # other widths: interpret mode only
    return pltpu.VMEM((hd // 128, T, 128) if wide else (T, hd), jnp.float32)


def _stage(dst, slab):
    """The slab (T, hd), widened to float32, into its staging."""
    if len(dst.shape) == 2:
        dst[...] = slab.astype(jnp.float32)
        return
    for c in range(dst.shape[0]):
        dst[c] = slab[:, c * 128:(c + 1) * 128].astype(jnp.float32)


def _head_rows(src, head):
    """The rows ``head`` (a strided ``pl.ds``) of a staged slab: (span,
    hd)."""
    if len(src.shape) == 2:
        return src[head, :]
    return jnp.concatenate([src.at[c][head, :] for c in range(src.shape[0])],
                           axis=1)


def _prefill_kernel(start_ref, end_ref, pages_ref, *refs,
                    table_pages: int, block_pages: int, page: int,
                    nkv: int, rep: int, probs_dtype, bounded: bool = False,
                    scale: float | None = None):
    """Float pool, one batch row's chunk.  ``start_ref``/``end_ref``
    (B,) and ``pages_ref`` (B * P,) are in SMEM: the chunk's first
    absolute position, one past the last position any of its rows may
    see, the flattened page table.  q_ref (1, n_kv, R, hd) holds each KV
    head's R = S * rep query rows, row ``s * rep + r``; pk_hbm/pv_hbm
    are the pools as (n_pages, page * n_kv, hd), left in HBM;
    k_buf/v_buf (2, T, hd) are the two halves of the double buffer of
    T = block_pages * page * n_kv slab rows, k32/v32 their float32
    staging (:func:`_staging`), acc_ref (n_kv, hd, R) the transposed float32
    accumulators, sems (2, 2) the K and V semaphores.  o_ref is
    (1, n_kv, R, hd) float32.  ``bounded``: one more scalar-prefetched
    operand leads ``refs``, ``lo_ref`` (B,), the first position the chunk's
    FIRST row sees (a window layer's; it may be negative): the band slides
    with the rows, row ``i`` sees from ``lo + i``, blocks wholly under
    ``lo`` are not copied and keys under a row's bound are masked."""
    lo_ref = refs[0] if bounded else None
    (q_ref, pk_hbm, pv_hbm, o_ref, k_buf, v_buf, k32, v32, acc_ref,
     sems) = refs[int(bounded):]
    b = pl.program_id(0)
    start, end = start_ref[b], end_ref[b]
    rows = page * nkv                          # pool rows a page
    span = block_pages * page                  # positions a block
    n_blocks = (end + span - 1) // span
    R, hd = q_ref.shape[2:]
    # the first block read, and how far back of its own position a row sees
    blk0 = jnp.maximum(lo_ref[b], 0) // span if bounded else 0
    back = start - lo_ref[b] if bounded else None

    def copies(blk, slot):
        out = []
        for i in range(block_pages):
            # a table that is no multiple of the block ends in repeats of
            # its last entry; their positions are past every live end
            at = jnp.minimum(blk * block_pages + i, table_pages - 1)
            pid = pages_ref[b * table_pages + at]
            dst = pl.ds(i * rows, rows)
            out.append(pltpu.make_async_copy(
                pk_hbm.at[pid], k_buf.at[slot, dst], sems.at[0, slot]))
            out.append(pltpu.make_async_copy(
                pv_hbm.at[pid], v_buf.at[slot, dst], sems.at[1, slot]))
        return out

    @pl.when(n_blocks > blk0)
    def _():
        for c in copies(blk0, blk0 % 2):
            c.start()

    # query row j (a lane) is chunk row j // rep: it sees positions up
    # to its own, and none at or past the live end (a padding row's own
    # position is past it: it sees what the last live row sees)
    lane_row = jax.lax.broadcasted_iota(jnp.int32, (1, R), 1) // rep
    q_last = jnp.minimum(start + lane_row, end - 1)           # (1, R)
    key_pos = jax.lax.broadcasted_iota(jnp.int32, (span, 1), 0)

    acc_ref[...] = jnp.zeros_like(acc_ref)

    def block(blk, carry):
        slot = blk % 2

        @pl.when(blk + 1 < n_blocks)
        def _():
            for c in copies(blk + 1, 1 - slot):
                c.start()

        for c in copies(blk, slot):
            c.wait()
        _stage(k32, k_buf[slot])
        _stage(v32, v_buf[slot])
        vis = blk * span + key_pos <= q_last                  # (span, R)
        if bounded:
            vis = jnp.logical_and(vis,
                                  blk * span + key_pos >= q_last - back)
        out = []
        for g in range(nkv):
            m, l = carry[g]
            head = pl.ds(g, span, stride=nkv)
            kg = _head_rows(k32, head).astype(k_buf.dtype)    # (span, hd)
            vg = _head_rows(v32, head).T.astype(v_buf.dtype)  # (hd, span)
            s = jax.lax.dot_general(
                kg, q_ref[0, g], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            s = s / math.sqrt(hd) if scale is None else s * scale
            s = jnp.where(vis, s, -1e30)
            # block 0 holds position 0, which every row sees, so m is a
            # real score from the first block on and the -1e30 of a
            # masked key underflows to exactly 0.  (Under a band a row may
            # see nothing of the first blocks: its m is then -1e30, its l
            # and acc finite, and the first block it does see multiplies
            # both by exp(-1e30 - m_new) = 0.)
            m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
            corr = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = l * corr + jnp.sum(p, axis=0, keepdims=True)
            pv = jnp.dot(vg, p.astype(probs_dtype),
                         preferred_element_type=jnp.float32)  # (hd, R)
            acc_ref[g] = acc_ref[g] * corr + pv
            out.append((m_new, l))
        return tuple(out)

    m0 = jnp.full((1, R), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((1, R), jnp.float32)
    stats = jax.lax.fori_loop(blk0, n_blocks, block, ((m0, l0),) * nkv)
    for g, (_, l) in enumerate(stats):
        o_ref[0, g] = (acc_ref[g] / jnp.where(l == 0.0, 1.0, l)).T


def paged_flash_prefill(qg, pk, pv, pages, apos, *, valid=None, lo=None,
                        scale: float | None = None,
                        probs_dtype=None, interpret: bool | None = None):
    """Chunked-prefill paged flash attention, pages read in place.

    qg (B, S, n_kv, rep, hd) grouped query (already rope'd); pk/pv
    (n_pages, page, n_kv, hd) float pools; pages (B, P) int32 page
    table; apos (B, S) int32 absolute positions of the chunk's rows,
    CONSECUTIVE from ``apos[:, 0]`` (a chunk is); valid (B, S) bool, True
    for the rows that belong to the prompt, a prefix of each batch row
    (default all); lo (B, S) int32, the first position each row sees,
    CONSECUTIVE too (a window layer's band; default: 0 for every row, and
    the program is the one without the operand); ``scale``: what the
    scores are multiplied by (default: divided by ``sqrt(hd)``).  A batch
    row reads its pages up to its last valid
    row's position and no further; one with no valid row reads nothing
    and gets zeros; a padding row's output is finite and means nothing.
    Returns f32 (B, S, n_kv, rep, hd), the value of the reference
    gather-then-einsum path to float32 summation order (caller applies
    the same ``astype`` epilogue).  ``interpret`` None: compiled on a
    TPU, interpreted elsewhere."""
    if pk.dtype == jnp.int8:
        raise ValueError("flash prefill is float-pool only (int8 "
                         "scale folding does not commute with the "
                         "online rescale)")
    B, S, nkv, rep, hd = qg.shape
    page = pk.shape[1]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if not interpret and not prefill_kernel_takes(pk.dtype, hd, page, S):
        raise ValueError(
            f"the flash prefill kernel does not compile for a {pk.dtype} "
            f"pool with head_dim {hd}, page_size {page} and a chunk of "
            f"{S} (prefill_kernel_takes); the engine's gather path "
            f"serves it")
    start = apos[:, 0]
    n_valid = S if valid is None else jnp.sum(valid.astype(jnp.int32), 1)
    # the band's lower bound slides with the rows: the first row's less
    # its clamp at 0 is what the kernel is told (``lo[:, -1]`` of the last)
    bound = () if lo is None else (lo[:, -1] - (S - 1),)
    return _prefill_float(
        qg, pk, pv, pages, start, start + n_valid, *bound,
        block_pages=min(PAGES_PER_BLOCK, pages.shape[1]),
        probs_dtype=jnp.dtype(probs_dtype or qg.dtype),
        interpret=bool(interpret),
        **({} if scale is None else {"scale": float(scale)}))


# jitted for the reason ``paged_attention._decode_float`` is: the layers
# of one prefill program share one trace and one Mosaic lowering
@functools.partial(jax.jit, static_argnames=("block_pages", "probs_dtype",
                                             "interpret", "scale"))
def _prefill_float(qg, pk, pv, pages, start, end, lo=None, *,
                   block_pages: int, probs_dtype, interpret: bool,
                   scale: float | None = None):
    """The kernel's call: qg (B, S, n_kv, rep, hd), the pools as the
    engine holds them, pages (B, P), start/end (B,): the chunk's first
    position and one past the last position it may see (0: nothing), and
    where given lo (B,): the first position the chunk's first row sees."""
    B, S, nkv, rep, hd = qg.shape
    P = pages.shape[1]
    n_pages, page = pk.shape[:2]
    R, rows = S * rep, page * nkv
    T = block_pages * rows
    bound = () if lo is None else (lo.astype(jnp.int32),)
    kernel = functools.partial(
        _prefill_kernel, table_pages=P, block_pages=block_pages, page=page,
        nkv=nkv, rep=rep, probs_dtype=probs_dtype, bounded=bool(bound),
        scale=scale)
    heads = pl.BlockSpec((1, nkv, R, hd), lambda b, *_: (b, 0, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3 + len(bound),
            grid=(B,),
            in_specs=[heads, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=heads,
            scratch_shapes=[pltpu.VMEM((2, T, hd), pk.dtype),
                            pltpu.VMEM((2, T, hd), pv.dtype),
                            _staging(T, hd),
                            _staging(T, hd),
                            pltpu.VMEM((nkv, hd, R), jnp.float32),
                            pltpu.SemaphoreType.DMA((2, 2))]),
        out_shape=jax.ShapeDtypeStruct((B, nkv, R, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(start.astype(jnp.int32), end.astype(jnp.int32),
      pages.reshape(-1).astype(jnp.int32), *bound,
      qg.transpose(0, 2, 1, 3, 4).reshape(B, nkv, R, hd),
      pk.reshape(n_pages, rows, hd), pv.reshape(n_pages, rows, hd))
    return out.reshape(B, nkv, S, rep, hd).transpose(0, 2, 1, 3, 4)
