"""The held experts' part of an expert layer's routed sum as a dropless
grouped product: only the (row, held expert) PAIRS the routing produced are
computed, sorted by expert, so that a row is multiplied by the experts it
chose and an expert no row chose is not read.

``models/mla_moe.expert_mlp`` is the one caller (the blocks of
``mla_moe``, ``gdn_moe`` and ``swa_moe``), under its scope ``moe_experts``.

**The plan** (:func:`plan_visits`, XLA) is made from the routing weights
``w_held`` (T, E) float32 alone, as ``mla_moe.route`` returns them: a pair
is an entry ``w_held > 0`` of a valid row, the same ``hit`` that
``mla_moe.moe_counts`` counts.  The pairs are sorted by expert and, within
an expert, by row (a counting sort: a pair's rank is the number of hits of
its expert in earlier rows, one triangular matrix product; no sort, gather
or scatter, each of which a TPU runs as a loop over indices), and an expert's
pairs are cut into row tiles of ``row_tile(T)``: a VISIT is one tile of one
expert, ``group_sizes`` (E,) int32 says how many pairs, so how many visits,
each expert has, and experts without a pair have none.  The list has the
static length ``max_visits`` (every pair of T x min(k, E) has a place
whatever the routing did, so no token is dropped and there is no capacity
to set); the kernel's grid is the visits that exist.  A visit carries its
expert, the tile row each row of the chunk takes in it (so a 0/1 selection
matrix, tile row x row of the chunk, is one comparison away), its pairs'
rows as a list, and their float32 routing weights.

**The product** (:func:`_visit_kernel`, Pallas; grid: visit x block of the
expert's width F) keeps the chunk's rows (T, H) and the float32 sum (T, H)
in VMEM for the whole call and streams the visited experts' matrices
``we_gate`` / ``we_up`` (E, H, F) and ``we_down`` (E, F, H) through it as
they lie, a block of F at a time.  A visit selects its rows with the 0/1
matrix (one exact MXU pass), computes ``g`` and ``u`` in the rows' dtype,
``silu(g) * u`` in the rows' dtype, accumulates the down product in
float32 over the blocks of F, multiplies each pair's row by its float32
routing weight and adds it onto the pair's row of the sum.  So a decode
step reads the matrices of touched experts only, a prefill chunk
multiplies an expert by a tile of the rows that chose it instead of by
every row, and neither writes a pair buffer to HBM: nothing is gathered or
scattered outside the kernel.

Off the chip :func:`routed_sum` runs the same plan as batched XLA matmuls
over the visits (the kernel's oracle and the CPU tests' form of the model;
``interpret=True`` runs the kernel itself in interpret mode).

Tile sizes follow from the row count and the expert's two widths
(:func:`row_tile`, :func:`width_block`, :func:`max_rows`); PERF.md section
6 (PR 39) has the chip's numbers behind them.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["routed_sum", "plan_visits", "Visits", "row_tile",
           "width_block", "max_rows", "max_visits"]

#: the kernel's scoped VMEM (the compiler's default is 16 MB): the rows and
#: the sum resident, three expert-matrix blocks twice, two tile scratches
VMEM_LIMIT_BYTES = 96 * 2 ** 20

#: elements of H x (block of F): three such blocks of bf16, each twice
#: (the pipeline's two buffers), are 24 MB
WEIGHT_BLOCK_ELEMS = 2 * 2 ** 20

#: elements of the resident rows (T, H): the rows' two buffers and the
#: float32 sum's two are 12 B an element, 40 MB
RESIDENT_ELEMS = 40 * 2 ** 20 // 12


def row_tile(rows: int) -> int:
    """Pairs a visit takes: 128 (an MXU pass of the v5e), or all of a
    smaller chunk's rows in whole 16-row tiles (bf16 packs 16 rows a
    sublane tile): an expert's pairs are at most the chunk's rows."""
    return min(128, -(-rows // 16) * 16)


def width_block(hidden: int, width: int) -> int:
    """Columns of an expert's width F a grid step takes: the largest
    multiple of 128 that divides F and keeps an (H, block) matrix block
    within ``WEIGHT_BLOCK_ELEMS``; F itself where it has no such divisor
    (interpret mode takes any)."""
    best = 0
    for b in range(128, width + 1, 128):
        if width % b == 0 and hidden * b <= WEIGHT_BLOCK_ELEMS:
            best = b
    return best or width


def max_rows(hidden: int) -> int:
    """Rows one kernel call keeps resident (whole 128-row tiles); a larger
    chunk is taken in pieces of this many rows."""
    return max(128, RESIDENT_ELEMS // hidden // 128 * 128)


def max_visits(rows: int, held: int, per_row: int) -> int:
    """The static length of the visit list: every full tile of the
    ``rows * per_row`` pairs there can be, one partial tile an expert, and
    no more than every expert by every tile of the rows."""
    tm = row_tile(rows)
    return min(rows * per_row // tm + held, held * -(-rows // tm))


class Visits(NamedTuple):
    """:func:`plan_visits`' plan, with ``tm = row_tile(T)`` and ``V =
    max_visits(T, E, per_row)``."""
    #: (E,) int32: pairs of each expert
    group_sizes: jax.Array
    #: () int32: visits that exist, ``sum(ceil(group_sizes / tm))``
    n: jax.Array
    #: (V,) int32: a visit's expert, ascending
    expert: jax.Array
    #: (V,) int32: a visit's pairs, 1 to ``tm`` (0 past ``n``)
    count: jax.Array
    #: (V, 1, T) int32: the tile row that row ``t`` of the chunk takes in
    #: the visit, in row order; negative or ``>= tm`` where the row is no
    #: pair of the visit
    slot: jax.Array
    #: (V * tm,) int32: the rows of a visit's tile rows, as a list (0 past
    #: ``count``)
    row: jax.Array
    #: (V, tm, 1) float32: the pairs' routing weights (0 past ``count``)
    weight: jax.Array


def plan_visits(w_held, valid, per_row: int) -> Visits:
    """The routing's pairs as visits.  ``w_held`` (T, E) float32,
    ``valid`` (T,) bool or None, ``per_row`` the most experts a row can
    have chosen here.

    Nothing here sorts, gathers or scatters (on a TPU each of those is a
    loop over its indices): an expert's tiles, a visit's expert and a
    pair's rank are comparisons against running sums, and a visit's rows
    are read off the comparison ``slot == tile row``."""
    T, E = w_held.shape
    tm, V = row_tile(T), max_visits(T, E, per_row)
    hit = w_held > 0
    if valid is not None:
        hit = jnp.logical_and(hit, valid[:, None])
    sizes = jnp.sum(hit, axis=0, dtype=jnp.int32)
    tiles = (sizes + tm - 1) // tm
    e = jnp.arange(E, dtype=jnp.int32)
    ends = jnp.sum(jnp.where(e[None, :] <= e[:, None], tiles[None, :], 0),
                   axis=1)
    starts = ends - tiles
    visit = jnp.arange(V, dtype=jnp.int32)[:, None]
    mine = jnp.logical_and(visit >= starts[None, :], visit < ends[None, :])
    pick = lambda a: jnp.sum(jnp.where(mine, a[None, :], 0), axis=1)  # noqa: E731
    expert = pick(e)
    first = (visit[:, 0] - pick(starts)) * tm       # rank of its first pair
    count = jnp.clip(pick(sizes) - first, 0, tm)
    # a pair's rank within its expert: hits of that expert in earlier rows
    # (0/1 products summed in float32: exact)
    earlier = jnp.tril(jnp.ones((T, T), jnp.bfloat16), -1)
    rank = jnp.dot(earlier, hit.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32).astype(jnp.int32)
    column = lambda a, none: jnp.sum(  # noqa: E731  (T, E) -> (V, T)
        jnp.where(mine[:, :, None], a.T[None], none), axis=1)
    slot = column(jnp.where(hit, rank, -1) + 1, 0) - 1 - first[:, None]
    select = slot[:, None, :] == jnp.arange(tm, dtype=jnp.int32)[None, :,
                                                                 None]
    row = jnp.sum(jnp.where(select, jnp.arange(T, dtype=jnp.int32), 0),
                  axis=2)
    weight = jnp.sum(jnp.where(select, column(w_held, 0.0)[:, None, :],
                               0.0), axis=2)
    return Visits(sizes, ends[-1], expert, count, slot[:, None, :],
                  row.reshape(-1), weight[:, :, None])


def _visit_kernel(expert_ref, count_ref, row_ref, slot_ref, wt_ref, x_ref,
                  wg_ref, wu_ref, wd_ref, out_ref, xt_ref, acc_ref):
    """One visit (grid axis 0) and one block of the expert's width (axis
    1).  ``expert_ref``, ``count_ref`` (V,), ``row_ref`` (V * tm,) int32
    in SMEM; slot_ref (1, 1, T) int32; wt_ref (1, tm, 1) float32; x_ref (T, H)
    and out_ref (T, H) float32, the same block at every step; wg_ref,
    wu_ref (H, tf) and wd_ref (tf, H) of the visit's expert; xt_ref (tm,
    H) the visit's rows, acc_ref (tm, H) float32 their down product."""
    del expert_ref                      # the index maps read it
    i, j = pl.program_id(0), pl.program_id(1)
    tm = xt_ref.shape[0]
    dt = xt_ref.dtype

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(j == 0)
    def _():
        # the visit's rows, selected by a 0/1 matrix: one exact MXU pass
        select = slot_ref[0] == lax.broadcasted_iota(
            jnp.int32, (tm, x_ref.shape[0]), 0)
        xt_ref[...] = jnp.dot(select.astype(dt), x_ref[...],
                              preferred_element_type=jnp.float32).astype(dt)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xt = xt_ref[...]
    g = jnp.dot(xt, wg_ref[...], preferred_element_type=jnp.float32)
    u = jnp.dot(xt, wu_ref[...], preferred_element_type=jnp.float32)
    # silu(g) * u with each op's result rounded to the rows' dtype, as XLA
    # rounds them; the arithmetic itself in float32 (the v5e's VPU has no
    # bf16, and Mosaic refuses ``logistic`` on a bf16 vector)
    f32 = jnp.float32
    g, u = g.astype(dt).astype(f32), u.astype(dt).astype(f32)
    h = (g * jax.nn.sigmoid(g).astype(dt).astype(f32)).astype(dt)
    h = (h.astype(f32) * u).astype(dt)
    acc_ref[...] += jnp.dot(h, wd_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        acc_ref[...] = acc_ref[...] * wt_ref[0]

        def add(r, carry):
            t = row_ref[i * tm + r]
            out_ref[pl.ds(t, 1), :] += acc_ref[pl.ds(r, 1), :]
            return carry

        lax.fori_loop(0, count_ref[i], add, 0)


# jitted so that the expert layers of one program share one trace and one
# Mosaic lowering, as the paged kernels' calls do
@functools.partial(jax.jit, static_argnames=("interpret",))
def _visit_call(plan: Visits, x, wg, wu, wd, *, interpret: bool):
    (T, H), F = x.shape, wg.shape[2]
    tm, tf = plan.weight.shape[1], width_block(H, F)
    whole = lambda i, j, *_: (0, 0)  # noqa: E731
    out = pl.pallas_call(
        _visit_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(plan.n, F // tf),
            in_specs=[
                pl.BlockSpec((1, 1, T), lambda i, j, *_: (i, 0, 0)),
                pl.BlockSpec((1, tm, 1), lambda i, j, *_: (i, 0, 0)),
                pl.BlockSpec((T, H), whole),
                pl.BlockSpec((None, H, tf), lambda i, j, e, *_: (e[i], 0, j)),
                pl.BlockSpec((None, H, tf), lambda i, j, e, *_: (e[i], 0, j)),
                pl.BlockSpec((None, tf, H), lambda i, j, e, *_: (e[i], j, 0)),
            ],
            out_specs=pl.BlockSpec((T, H), whole),
            scratch_shapes=[pltpu.VMEM((tm, H), x.dtype),
                            pltpu.VMEM((tm, H), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((T, H), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(plan.expert, plan.count, plan.row, plan.slot, plan.weight, x, wg, wu,
      wd)
    # with no visit the grid is empty and the sum was never written
    return jnp.where(plan.n > 0, out, 0.0)


def _visits_xla(plan: Visits, x, wg, wu, wd):
    """The plan's product as batched matmuls over ALL ``max_visits``
    visits, each with its expert's matrices gathered: the kernel's
    arithmetic (a visit past the last one has weight 0 in every tile
    row and adds 0)."""
    tm = plan.weight.shape[1]
    select = (plan.slot == jnp.arange(tm, dtype=jnp.int32)[None, :, None]
              ).astype(x.dtype)
    xt = jnp.einsum("vrt,th->vrh", select, x)
    g = jnp.einsum("vrh,vhf->vrf", xt, wg[plan.expert])
    u = jnp.einsum("vrh,vhf->vrf", xt, wu[plan.expert])
    y = jnp.einsum("vrf,vfh->vrh", jax.nn.silu(g) * u, wd[plan.expert],
                   preferred_element_type=jnp.float32)
    return jnp.einsum("vrt,vrh->th", select.astype(jnp.float32),
                      y * plan.weight, precision=lax.Precision.HIGHEST)


def routed_sum(rows, w_held, we_gate, we_up, we_down, *, per_row: int,
               valid=None, interpret: bool | None = None):
    """``sum_e w_held[t, e] * SwiGLU_e(rows[t])`` over the held experts a
    valid row chose, (T, H) float32.  ``rows`` (T, H); ``w_held`` (T, E)
    float32, zero where a row did not choose an expert; the experts'
    matrices (E, H, F), (E, H, F), (E, F, H); ``per_row`` the most experts
    a row can have chosen (``min(num_experts_per_tok, E)``); an invalid
    row's sum is 0.  ``interpret`` None: the Pallas kernel on a TPU, the
    plan's XLA form elsewhere; True or False: the kernel, interpreted or
    compiled."""
    T, H = rows.shape
    if interpret is None and jax.default_backend() != "tpu":
        return _visits_xla(plan_visits(w_held, valid, per_row), rows,
                           we_gate, we_up, we_down)
    if T > max_rows(H):     # more rows than stay resident: in pieces
        cut = max_rows(H)
        return jnp.concatenate([
            routed_sum(rows[a:a + cut], w_held[a:a + cut], we_gate, we_up,
                       we_down, per_row=per_row, interpret=interpret,
                       valid=None if valid is None else valid[a:a + cut])
            for a in range(0, T, cut)])
    return _visit_call(plan_visits(w_held, valid, per_row), rows, we_gate,
                       we_up, we_down, interpret=bool(interpret))
