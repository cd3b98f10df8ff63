"""The gated delta rule's decode step (Pallas): one token of the recurrence
of ``models/gdn_hybrid.py`` for every LIVE batch slot, the slot's state
read once and written once, in place.

The state slots are stored lane-dense (``gdn_hybrid.slot_shape``):
``(n_slots, dk, n * dv)``, key dim on the sublanes, the heads' value dims
side by side on the lanes, so that a slot is whole (8, 128) tiles at the
published widths (96 x 5,760) and a block copy moves no padding.  One grid
step is one slot: the pipeline copies its state to VMEM, the body does, in
float32 on the VPU, a group of heads at a time (``head_group``: the fewest
heads whose value dims fill whole lane tiles, two of 192 or of 128)::

    s = alpha * S;  u = beta * (v - k^T s);  S' = s + k u^T;  o = q^T S'

and the pipeline copies ``S'`` back over ``S``: the state is aliased in
and out, so no slot is copied to make the output and a slot the grid does
not visit is bit-unchanged because nothing writes it.  A state is never
rounded for an MXU pass; the sums over ``dk`` are sublane reductions.

**Live slots only.**  A slot none of whose heads has ``beta != 0`` or
``g != 0`` is one the mathematics leaves as it was (``linear_inputs``
zeroes both where a row is not valid), and the kernel leaves its memory
alone as well: the slot's grid step maps to the block of the last live
slot before it (``visit_list``, by scalar prefetch), which the pipeline
neither fetches nor writes back a second time, and its body only writes
zeros to the slot's row of ``o``.

The small operands come prepared by XLA, inside the caller's scope:
``k | q`` transposed to ``(B, dk, 2 n_k)`` (a KEY head's vector down the
sublanes, broadcast in the kernel along the lanes of each value head it
serves: value head ``r`` reads column ``r // (n / n_k)``, grouped value
heads) and ``alpha``, ``beta`` (repeated over a value head's lanes) and
``v`` as rows ``(3, B, n * dv)``, taken eight slots a block (B padded up to
whole blocks).  Equal to the XLA form of
``gdn_hybrid.recurrent_step`` to float32 summation order
(tests/test_kernels.py, interpret mode on the CPU; chip_smoke.py on the
chip).  :func:`step_kernel_takes` states which shapes compile on a TPU;
interpret mode takes any.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["gdn_decode_step", "step_kernel_takes", "head_group",
           "visit_list"]

#: slots whose rows of ``alpha | beta | v`` and of ``o`` share one block:
#: a float32 sublane tile
ROW_BLOCK = 8

#: the kernel's scoped VMEM: a slot's state twice in and twice out
#: (8.8 MB at the published widths) and the body's temporaries, where the
#: compiler's default is 16 MB
VMEM_LIMIT_BYTES = 48 * 2 ** 20


def head_group(n: int, dv: int) -> int:
    """Heads the body takes at a time: the fewest whose value dims fill
    whole 128-lane tiles, and more than one of them (Mosaic refuses the
    body's read of ONE slot's row of ``alpha | beta | v`` at a width of a
    single tile: "dynamic load with unaligned indices"; two heads of 128
    compile, as two of 192 do), where the head count is a multiple of
    that; else all of them (a group then ends inside a tile, which only
    interpret mode takes)."""
    g = math.lcm(dv, 128) // dv
    if g * dv == 128:
        g *= 2
    return g if n % g == 0 else n


def step_kernel_takes(n: int, dk: int, dv: int) -> bool:
    """The shapes :func:`_step_kernel` compiles for on a TPU: a group of
    heads is whole lane tiles and the key dim whole sublane tiles, whatever
    the batch.  Interpret mode takes any."""
    return (head_group(n, dv) * dv) % 128 == 0 and dk % 8 == 0


def visit_list(live):
    """live (B,) bool -> the state block each grid step maps to, (B,)
    int32: a live slot's own, a dead slot's the last live slot before it
    (the block the pipeline already holds: no copy either way), and before
    the first live slot that one's (fetched once, and used).  All 0 when
    nothing is live."""
    idx = jnp.arange(live.shape[0], dtype=jnp.int32)
    last = lax.cummax(jnp.where(live, idx, -1))
    return jnp.where(last >= 0, last, jnp.argmax(live).astype(jnp.int32))


def _step_kernel(src_ref, live_ref, kq_ref, rows_ref, s_ref, o_ref,
                 s_out_ref, *, n: int, dv: int, group: int):
    """One batch slot.  ``src_ref``, ``live_ref`` (B,) int32 in SMEM;
    kq_ref (1, dk, 2 n_k): k then q of slot ``src_ref[i]``, a key head a
    column; rows_ref (3, R, n dv): alpha, beta, v of the R slots of this
    row block; s_ref / s_out_ref (1, dk, n dv): the state of slot
    ``src_ref[i]``, in and out (one buffer in HBM); o_ref (R, n dv)."""
    i = pl.program_id(0)
    r = i % o_ref.shape[0]
    row = pl.ds(r, 1)
    W = group * dv
    lane = lax.broadcasted_iota(jnp.int32, (1, W), 1)
    n_k = kq_ref.shape[2] // 2
    per_key = n // n_k          # value heads that read one key head's column

    def columns(base, first):
        """The columns of kq_ref (k from ``base`` 0, q from ``n_k``) that
        value heads ``first .. first + group`` read, each broadcast along
        its head's own dv lanes: (dk, W)."""
        col = lambda h: base + h // per_key  # noqa: E731
        out = kq_ref[0, :, col(first):col(first) + 1]
        for t in range(1, group):
            if col(first + t) != col(first + t - 1):
                c = col(first + t)
                out = jnp.where(lane >= t * dv, kq_ref[0, :, c:c + 1], out)
        return out

    @pl.when(live_ref[i] != 0)
    def _():
        for p in range(n // group):
            cols = slice(p * W, (p + 1) * W)
            k, q = columns(0, p * group), columns(n_k, p * group)
            s = rows_ref[0, row, cols] * s_ref[0, :, cols]
            u = rows_ref[1, row, cols] * (
                rows_ref[2, row, cols] - jnp.sum(k * s, axis=0,
                                                 keepdims=True))
            s = s + k * u
            s_out_ref[0, :, cols] = s
            o_ref[row, cols] = jnp.sum(q * s, axis=0, keepdims=True)

    @pl.when(live_ref[i] == 0)
    def _():
        o_ref[row, :] = jnp.zeros((1, o_ref.shape[1]), o_ref.dtype)

        # the first step's output buffer holds nothing yet: if no live step
        # of the same block follows (nothing is live at all), what the
        # pipeline writes back at the end must be the state
        @pl.when(i == 0)
        def _():
            s_out_ref[...] = s_ref[...]


def gdn_decode_step(q, k, v, g, beta, state, *,
                    interpret: bool | None = None):
    """One token of the gated delta rule for every slot, live slots' state
    moved once in and once out, in place.

    q, k (B, n_k, dk), v (B, n, dv), g, beta (B, n), all float32, ``n`` a
    multiple of ``n_k`` (value head ``r`` reads key head ``r // (n /
    n_k)``); ``state`` (B, dk, n * dv) float32, the slots as stored
    (``gdn_hybrid.slot_shape``).  Returns ``o`` (B, n, dv) and the new
    state, which is ``state``'s buffer where the caller donates it.  A
    slot whose ``g`` and ``beta`` are all 0 is not read or written and
    gets ``o = 0``.  ``interpret`` None: compiled on a TPU, interpreted
    elsewhere."""
    B, n, dv = v.shape
    dk = q.shape[-1]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if not interpret and not step_kernel_takes(n, dk, dv):
        raise ValueError(
            f"the gated delta rule's step kernel does not compile for "
            f"{n} heads x {dk} x {dv} (step_kernel_takes); "
            f"gdn_hybrid.recurrent_step's XLA form serves them")
    live = jnp.any(jnp.logical_or(beta != 0, g != 0), axis=-1)
    kq = jnp.concatenate([k, q], axis=1).transpose(0, 2, 1)
    rows = jnp.stack([jnp.repeat(jnp.exp(g), dv, axis=-1),
                      jnp.repeat(beta, dv, axis=-1), v.reshape(B, n * dv)])
    if B % ROW_BLOCK:       # whole row blocks; the grid stays B steps
        rows = jnp.pad(rows, ((0, 0), (0, -B % ROW_BLOCK), (0, 0)))
    o, state = _step(visit_list(live), live.astype(jnp.int32), kq, rows,
                     state, n=n, interpret=bool(interpret))
    return o[:B].reshape(B, n, dv), state


# jitted so that the linear layers of one decode program share one trace
# and one Mosaic lowering, as the paged kernels' calls do
@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def _step(src, live, kq, rows, state, *, n: int, interpret: bool):
    B, dk, width = state.shape
    dv = width // n
    R = ROW_BLOCK           # rows.shape[1] is B, padded up to whole blocks
    slot = pl.BlockSpec((1, dk, width), lambda i, src, live: (src[i], 0, 0))
    return pl.pallas_call(
        functools.partial(_step_kernel, n=n, dv=dv,
                          group=head_group(n, dv)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, dk, kq.shape[2]),
                             lambda i, src, live: (src[i], 0, 0)),
                pl.BlockSpec((3, R, width), lambda i, *_: (0, i // R, 0)),
                slot],
            out_specs=[pl.BlockSpec((R, width), lambda i, *_: (i // R, 0)),
                       slot]),
        out_shape=[jax.ShapeDtypeStruct(rows.shape[1:], jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 4 (after the two prefetched scalars, kq, rows) is the
        # state, output 1 its new value: one buffer
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(src, live, kq, rows, state)
