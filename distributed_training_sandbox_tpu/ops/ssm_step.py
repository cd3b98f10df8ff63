"""The Mamba-2 recurrence's decode step (Pallas): one token of
``models/ssm_moe.py``'s recurrence for every LIVE batch slot, the slot's
state read once and written once, in place.

The state slots are stored ``(n_slots, ds, n * hd)`` (``ssm_moe.
slot_shape``): the state dim down the sublanes, the heads' dims side by
side on the lanes, whole (8, 128) tiles at the published widths
(128 x 8,192).  A slot's state is 4.19 MB, so in and out, double-buffered,
a whole slot would be the compiler's whole default scoped VMEM: the grid
walks LANE GROUPS of a slot (``lane_group``: 2,048 lanes = 32 heads, 1 MB a
block), the group outermost and the slots inside it.  One grid step is one
group of one slot: the pipeline copies the block to VMEM, the body does, in
float32 on the VPU, ``LANE_STEP`` lanes at a time::

    S' = a * S + B (x) xd;    o = C^T S'

(``a`` a head's decay repeated over its lanes, a row broadcast down the
sublanes; ``B``, ``C`` columns broadcast along the lanes; the sum over
``ds`` a sublane reduction), and the pipeline copies ``S'`` back over
``S``: the state is aliased in and out, so no slot is copied to make the
output and a slot the grid does not visit is bit-unchanged because nothing
writes it.  A state is never rounded for an MXU pass.  The ``Dskip`` term
is the caller's (``ssm_moe.recurrent_step`` adds it to ``o``).

**Live slots only**, as ``ops/gdn_step.py`` does it and with its
:func:`visit_list`: a slot whose ``g`` and ``xd`` are all 0 is one the
mathematics leaves as it was (``ssm_moe.linear_inputs`` zeroes both where
a row is not valid); its grid steps map to the block of the last live slot
before it in the same lane group, which the pipeline neither fetches nor
writes back a second time, and its body only writes zeros to the slot's row
of ``o``.

The small operands come prepared by XLA, inside the caller's scope: ``B |
C`` as columns ``(slots, ds, 2)``, and ``a`` (repeated over a head's lanes)
and ``xd`` as rows ``(2, slots, n * hd)``, taken eight slots a block (the
slots padded up to whole blocks), fetched once a lane group.  Equal to the
XLA form of ``ssm_moe.recurrent_step`` to float32 summation order
(tests/test_ssm_moe.py, interpret mode on the CPU).
:func:`step_kernel_takes` states which shapes compile on a TPU; interpret
mode takes any.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .gdn_step import ROW_BLOCK, visit_list

__all__ = ["ssm_decode_step", "step_kernel_takes", "lane_group"]

#: lanes of a slot's state one grid step takes: 128 sublanes x 2,048 lanes
#: of float32 are 1 MB, 4 MB in and out double-buffered
LANE_GROUP = 2048

#: lanes the body takes at a time (two lane tiles: Mosaic refuses the
#: dynamic read of ONE slot's row of ``a | xd`` at the width of a single
#: tile, as ``gdn_step.head_group`` found)
LANE_STEP = 256

VMEM_LIMIT_BYTES = 32 * 2 ** 20


def lane_group(width: int) -> int:
    """Lanes of a slot a grid step takes: ``LANE_GROUP`` where that divides
    the slot's ``width``, else the whole slot."""
    return LANE_GROUP if width % LANE_GROUP == 0 else width


def step_kernel_takes(n: int, hd: int, ds: int) -> bool:
    """The shapes :func:`_step_kernel` compiles for on a TPU: a lane group
    is whole steps of ``LANE_STEP`` lanes and the state dim whole sublane
    tiles, whatever the batch.  Interpret mode takes any."""
    return lane_group(n * hd) % LANE_STEP == 0 and ds % 8 == 0


def _step_kernel(src_ref, live_ref, bc_ref, rows_ref, s_ref, o_ref,
                 s_out_ref):
    """One lane group of one batch slot.  ``src_ref``, ``live_ref`` (B,)
    int32 in SMEM; bc_ref (1, ds, 2): B then C of slot ``src_ref[i]``, a
    column each; rows_ref (2, R, W): a and xd of the R slots of this row
    block, this group's lanes; s_ref / s_out_ref (1, ds, W): the group's
    lanes of the state of slot ``src_ref[i]``, in and out (one buffer in
    HBM); o_ref (R, W)."""
    i = pl.program_id(1)        # the slot; axis 0 is the lane group
    row = pl.ds(i % o_ref.shape[0], 1)
    W = s_ref.shape[2]
    step = min(LANE_STEP, W)

    @pl.when(live_ref[i] != 0)
    def _():
        b, c = bc_ref[0, :, 0:1], bc_ref[0, :, 1:2]              # (ds, 1)
        for p in range(W // step):
            cols = slice(p * step, (p + 1) * step)
            s = rows_ref[0, row, cols] * s_ref[0, :, cols] \
                + b * rows_ref[1, row, cols]
            s_out_ref[0, :, cols] = s
            o_ref[row, cols] = jnp.sum(c * s, axis=0, keepdims=True)

    @pl.when(live_ref[i] == 0)
    def _():
        o_ref[row, :] = jnp.zeros((1, W), o_ref.dtype)

        # the first step's output buffer holds nothing yet: if no live step
        # of the same block follows (nothing is live at all), what the
        # pipeline writes back at the end must be the state
        @pl.when(i == 0)
        def _():
            s_out_ref[...] = s_ref[...]


def ssm_decode_step(xd, Bm, Cm, g, state, *, interpret: bool | None = None):
    """One token of the Mamba-2 recurrence for every slot, live slots'
    state moved once in and once out, in place.

    xd (B, n, hd), Bm, Cm (B, ds), g (B, n), all float32; ``state`` (B, ds,
    n * hd) float32, the slots as stored (``ssm_moe.slot_shape``).  Returns
    ``o = C^T S'`` (B, n, hd), without the ``Dskip`` term, and the new
    state, which is ``state``'s buffer where the caller donates it.  A slot
    whose ``g`` and ``xd`` are all 0 is not read or written and gets ``o =
    0``.  ``interpret`` None: compiled on a TPU, interpreted elsewhere."""
    B, n, hd = xd.shape
    ds = Bm.shape[-1]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if not interpret and not step_kernel_takes(n, hd, ds):
        raise ValueError(
            f"the Mamba-2 step kernel does not compile for {n} heads x "
            f"{hd} x {ds} (step_kernel_takes); ssm_moe.recurrent_step's "
            f"XLA form serves them")
    live = jnp.logical_or(jnp.any(g != 0, axis=-1),
                          jnp.any(xd != 0, axis=(1, 2)))
    rows = jnp.stack([jnp.repeat(jnp.exp(g), hd, axis=-1),
                      xd.reshape(B, n * hd)])
    if B % ROW_BLOCK:       # whole row blocks; the grid stays B slots
        rows = jnp.pad(rows, ((0, 0), (0, -B % ROW_BLOCK), (0, 0)))
    o, state = _step(visit_list(live), live.astype(jnp.int32),
                     jnp.stack([Bm, Cm], axis=-1), rows, state,
                     interpret=bool(interpret))
    return o[:B].reshape(B, n, hd), state


# jitted so that the Mamba-2 layers of one decode program share one trace
# and one Mosaic lowering, as the paged kernels' calls do
@functools.partial(jax.jit, static_argnames=("interpret",))
def _step(src, live, bc, rows, state, *, interpret: bool):
    B, ds, width = state.shape
    R, W = ROW_BLOCK, lane_group(width)
    slot = pl.BlockSpec((1, ds, W), lambda j, i, src, live: (src[i], 0, j))
    return pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(width // W, B),
            in_specs=[
                pl.BlockSpec((1, ds, 2),
                             lambda j, i, src, live: (src[i], 0, 0)),
                pl.BlockSpec((2, R, W), lambda j, i, *_: (0, i // R, j)),
                slot],
            out_specs=[pl.BlockSpec((R, W), lambda j, i, *_: (i // R, j)),
                       slot]),
        out_shape=[jax.ShapeDtypeStruct(rows.shape[1:], jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 4 (after the two prefetched scalars, bc, rows) is the
        # state, output 1 its new value: one buffer
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(src, live, bc, rows, state)
