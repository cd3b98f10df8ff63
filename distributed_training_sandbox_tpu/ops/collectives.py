"""L1 communication backend: explicit XLA collectives over a named mesh.

The reference's L1 is ``torch.distributed`` over NCCL; the complete set of
collectives it exercises (SURVEY.md §2.3) maps 1:1 onto ``jax.lax`` ops used
*inside* ``shard_map``:

    dist.all_reduce            -> lax.psum / pmax / pmin (all_reduce here)
    dist.broadcast             -> masked psum (broadcast here; NCCL's own
                                  barrier trick in reverse — reference
                                  README.md:11 notes barriers ARE all_reduces)
    dist.all_gather(_into_tensor) -> lax.all_gather
    dist.reduce_scatter_tensor -> lax.psum_scatter
    dist.send/recv/isend/irecv -> lax.ppermute (ring / point-to-point)
    dist.all_to_all            -> lax.all_to_all
    dist.barrier               -> 1-element psum (barrier here)
    dist.scatter               -> psum_scatter of a masked stack, or slicing
                                  of a broadcast — provided as ``scatter``

These wrappers exist so strategy code reads like the reference's choreography
and so traces/HLO show one collective per logical call (shard_map keeps XLA
from re-choreographing them — SURVEY.md §7.1).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def smap(f, mesh: Mesh, in_specs, out_specs, **kw):
    """shard_map with this repo's defaults (explicit collectives allowed)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False, **kw)


def axis_rank(axis_name: str) -> jax.Array:
    """Device's coordinate along ``axis_name`` — the in-SPMD 'rank'."""
    return lax.axis_index(axis_name)


def axis_size(axis_name: str) -> int:
    """Static size of the named mesh axis (a trace-time Python int),
    usable inside shard_map/pmap."""
    return lax.axis_size(axis_name)


def all_reduce(x, axis_name: str, op: str = "sum", *, mean: bool = False):
    """Twin of ``dist.all_reduce`` with SUM/MAX/MIN/PRODUCT (reference
    ``DDP/ddp.py:46``, ``02-operations.ipynb`` cells 33-36).  ``mean=True``
    fuses the reference's all_reduce-then-divide-by-ws DDP idiom."""
    if op == "sum":
        out = lax.psum(x, axis_name)
    elif op == "max":
        out = lax.pmax(x, axis_name)
    elif op == "min":
        out = lax.pmin(x, axis_name)
    elif op in ("prod", "product"):
        # No pprod primitive: product = sign-corrected exp(sum(log|x|)).
        # Costs 3 psums (magnitude, sign parity, zero detection) but handles
        # negatives/zeros like dist.all_reduce(PRODUCT); prod is a teaching
        # op (02-operations.ipynb cell 36), never on a hot path.
        neg = lax.psum((x < 0).astype(jnp.float32), axis_name)
        has_zero = lax.pmax((x == 0).astype(jnp.float32), axis_name)
        mag = jnp.exp(lax.psum(jnp.log(jnp.abs(jnp.where(x == 0, 1, x))),
                               axis_name))
        sign = jnp.where(neg % 2 == 1, -1.0, 1.0)
        out = jnp.where(has_zero > 0, 0.0, sign * mag).astype(x.dtype)
    else:
        raise ValueError(f"unknown reduce op {op!r}")
    if mean:
        if op != "sum":
            raise ValueError("mean only makes sense with sum")
        out = out / axis_size(axis_name)
    return out


def all_gather(x, axis_name: str, *, axis: int = 0, tiled: bool = True):
    """Twin of ``dist.all_gather_into_tensor`` (reference ``zero/zero3.py:39``):
    concatenate every device's shard along ``axis``."""
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name: str, *, axis: int = 0, tiled: bool = True):
    """Twin of ``dist.reduce_scatter_tensor`` (reference ``zero/zero2.py:107``):
    sum across devices, each device keeps its ``axis``-chunk."""
    return lax.psum_scatter(x, axis_name, scatter_dimension=axis, tiled=tiled)


def broadcast(x, axis_name: str, root=0):
    """Twin of ``dist.broadcast`` (reference ``DDP/ddp.py:36``,
    ``zero/zero1.py:102``): every device receives root's value.

    Implemented as a masked psum — one all-reduce on the wire, which is how
    NCCL traces also account small broadcasts/barriers (reference
    README.md:11-12).  ``root`` may be traced (zero1 recomputes the owner
    rank arithmetically per param, ``zero1.py:91-102``)."""
    mask = (lax.axis_index(axis_name) == root)
    zeros = jax.tree.map(jnp.zeros_like, x)
    masked = jax.tree.map(lambda a, z: jnp.where(mask, a, z), x, zeros)
    return jax.tree.map(lambda a: lax.psum(a, axis_name), masked)


def scatter(x, axis_name: str, *, axis: int = 0):
    """Twin of ``dist.scatter`` (nb cell 30): root's tensor split into
    equal chunks, one per device.  SPMD formulation: every device slices its
    own chunk of the (already broadcast) input."""
    idx = lax.axis_index(axis_name)
    n = axis_size(axis_name)
    if x.shape[axis] % n:
        raise ValueError(f"scatter: dim {axis} of size {x.shape[axis]} not "
                         f"divisible by axis {axis_name!r} size {n}")
    chunk = x.shape[axis] // n
    return lax.dynamic_slice_in_dim(x, idx * chunk, chunk, axis=axis)


def ppermute_ring(x, axis_name: str, *, shift: int = 1):
    """Ring send/recv: device i sends to (i+shift) mod n — the twin of the
    reference's send/recv pairs (``02-operations.ipynb`` cells 11-21) and of
    pipeline stage hops."""
    n = axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm)


def all_to_all(x, axis_name: str, *, split_axis: int = 0, concat_axis: int = 0,
               tiled: bool = True):
    return lax.all_to_all(x, axis_name, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=tiled)


# ------------------------------------------------------- ring decomposition
#
# The overlap engine's primitives (SimpleFSDP, arXiv:2411.00284): the same
# bytes the monolithic all_gather / psum_scatter / psum ops move, but
# decomposed into ppermute ring hops the XLA scheduler can interleave with
# compute — a monolithic collective is an opaque wall; n-1 hops with a
# matmul chunk between each are a pipeline.  Two exactness classes:
#
#   * ``ring_all_gather`` and ``decomposed_all_reduce`` are BITWISE equal
#     to their monolithic twins: the ring moves data without arithmetic
#     (chunks land in rank order), the reduction arithmetic stays in the
#     monolithic psum_scatter (same per-element reduction order as psum —
#     pinned by tests/test_overlap.py), and their custom_vjp backward IS
#     the monolithic op's transpose.  These power ``--overlap ring``,
#     whose loss sequences are bitwise-identical to ``--overlap none``.
#   * ``all_gather_matmul`` / ``matmul_reduce_scatter`` additionally fuse
#     the matmul into the ring (multiply the chunk already on device
#     while the next shard travels / scatter partial products as they
#     finish).  Chunked contraction reassociates the K-sum, so these are
#     numerically equivalent but NOT bitwise — they power
#     ``--overlap ring_fused``.


class RingShard:
    """A weight that stays SHARDED along its contraction dim: the marker
    ``parallel.fsdp``'s ring_fused layer hook hands to the model so the
    projection matmul runs as ``all_gather_matmul`` instead of
    gather-then-matmul.  Registered as a pytree so it rides through scan
    / remat / AD like the plain array it replaces.

    ``impl`` selects the per-chunk matmul engine: ``"xla"`` (the plain
    traced ``@``) or ``"pallas"`` (:func:`all_gather_matmul_pallas`'s
    tile kernel) — aux data, so the two variants trace as distinct
    programs."""

    def __init__(self, shard, axis_name: str, impl: str = "xla"):
        self.shard = shard
        self.axis_name = axis_name
        self.impl = impl

    def __repr__(self):  # pragma: no cover - debugging aid
        return (f"RingShard({self.shard.shape}, axis={self.axis_name!r}, "
                f"impl={self.impl!r})")


jax.tree_util.register_pytree_node(
    RingShard,
    lambda rs: ((rs.shard,), (rs.axis_name, rs.impl)),
    lambda aux, children: RingShard(children[0], *aux))


def _ring_perm(n: int, shift: int = 1):
    return [(i, (i + shift) % n) for i in range(n)]


def _check_chunk(name: str, what: str, size: int, n: int, axis_name: str):
    """Explicit divisibility guard: the ring splits ``what`` into one
    chunk per device, so an indivisible dim would otherwise surface as an
    opaque reshape/dynamic-slice failure deep in the trace."""
    if size % n:
        raise ValueError(
            f"{name}: {what} of size {size} is not divisible by mesh "
            f"axis {axis_name!r} size {n} — the ring needs one "
            f"equal chunk per device (pad the dim or use the "
            f"monolithic collective)")


def _ring_gather_impl(x, axis_name: str, axis: int):
    """n-1 ppermute hops assembling shards in rank order — value-wise
    identical to ``lax.all_gather(tiled=True)`` (pure data movement)."""
    n = axis_size(axis_name)
    if n == 1:  # degenerate ring: nothing to gather
        return x
    idx = lax.axis_index(axis_name)
    axis = axis % x.ndim
    chunk = x.shape[axis]
    out = jnp.zeros(x.shape[:axis] + (n * chunk,) + x.shape[axis + 1:],
                    x.dtype)
    cur = x
    for t in range(n):
        src = (idx - t) % n          # whose shard arrived after t hops
        out = lax.dynamic_update_slice_in_dim(out, cur, src * chunk, axis)
        if t < n - 1:
            cur = lax.ppermute(cur, axis_name, _ring_perm(n))
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def ring_all_gather(x, axis_name: str, axis: int = 0):
    """Ring-decomposed twin of :func:`all_gather`: bitwise-identical
    output (rank-order chunk placement, zero arithmetic), backward pinned
    to the monolithic gather's transpose (one psum_scatter) so gradients
    are bitwise-identical too.  The n-1 exposed hops are what the
    latency-hiding scheduler overlaps with the compute consuming the
    early chunks."""
    return _ring_gather_impl(x, axis_name, axis)


def _rag_fwd(x, axis_name, axis):
    return _ring_gather_impl(x, axis_name, axis), None


def _rag_bwd(axis_name, axis, _res, g):
    if axis_size(axis_name) == 1:
        return (g,)
    return (lax.psum_scatter(g, axis_name, scatter_dimension=axis % g.ndim,
                             tiled=True),)


ring_all_gather.defvjp(_rag_fwd, _rag_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def decomposed_all_reduce(x, axis_name: str, axis: int = -1):
    """all_reduce split into psum_scatter + ring all-gather — the RS+AG
    identity EQuARX treats as first-class.  The reduction arithmetic
    stays in the monolithic psum_scatter (same per-element order as
    lax.psum — pinned by test), the re-assembly is the exact ring, so
    the value is BITWISE equal to ``lax.psum`` while exposing n-1
    schedulable hops.  Backward is pinned to psum's own transpose
    (a psum of the cotangent).  ``axis``: the dim to scatter over; must
    be divisible by the ring size."""
    n = axis_size(axis_name)
    if n == 1:
        return x
    axis = axis % x.ndim
    _check_chunk("decomposed_all_reduce", f"scatter dim {axis}",
                 x.shape[axis], n, axis_name)
    scattered = lax.psum_scatter(x, axis_name, scatter_dimension=axis,
                                 tiled=True)
    return _ring_gather_impl(scattered, axis_name, axis)


def _dar_fwd(x, axis_name, axis):
    return decomposed_all_reduce(x, axis_name, axis), None


def _dar_bwd(axis_name, axis, _res, g):
    # lax.psum transposes to lax.psum (replicated cotangent summed) —
    # keep the ring variant's backward identical to the baseline's
    return (lax.psum(g, axis_name),)


decomposed_all_reduce.defvjp(_dar_fwd, _dar_bwd)


def all_gather_matmul(a, w_shard, axis_name: str):
    """Decomposed collective matmul, gather side: ``a @ W`` where ``W``
    is the rank-order concatenation of ``w_shard`` (each device's rows
    of the contraction dim).  At ring step t the chunk already on device
    multiplies while the next shard travels — the all-gather never
    materializes as one op, so nothing blocks the MXU.

    Plain traceable code: its AD transpose IS the ring
    matmul-reduce-scatter (cotangent contributions ride the reversed
    ring and sum along the way), which is why the ring_fused FSDP
    backward needs no separate reduce-scatter.  Chunked contraction
    reassociates the K-sum: numerically equivalent, not bitwise.
    """
    n = axis_size(axis_name)
    if n == 1:   # degenerate ring: the shard IS the whole weight
        return a @ w_shard
    k_chunk = w_shard.shape[0]
    K = a.shape[-1]
    if K != n * k_chunk:
        raise ValueError(
            f"all_gather_matmul: activation contraction dim {K} != "
            f"mesh axis {axis_name!r} size {n} x weight shard rows "
            f"{k_chunk} — the shard must be a 1/{n} row-slice of the "
            f"full weight (got shard shape {tuple(w_shard.shape)})")
    idx = lax.axis_index(axis_name)
    acc = jnp.zeros(a.shape[:-1] + (w_shard.shape[1],),
                    jnp.promote_types(a.dtype, w_shard.dtype))
    cur = w_shard
    for t in range(n):
        src = (idx - t) % n
        a_chunk = lax.dynamic_slice_in_dim(a, src * k_chunk, k_chunk,
                                           axis=a.ndim - 1)
        acc = acc + a_chunk @ cur
        if t < n - 1:
            cur = lax.ppermute(cur, axis_name, _ring_perm(n))
    return acc.astype(a.dtype)


def _agmm_chunk_kernel(a_ref, w_ref, o_ref):
    # Mosaic's matmul accumulates in 32 bits or not at all
    o_ref[...] = jnp.dot(a_ref[...], w_ref[...],
                         preferred_element_type=jnp.float32
                         ).astype(o_ref.dtype)


def _agmm_tile_call(a2, w, out_dtype, block_m, block_n, interpret):
    """One ring chunk's matmul as a Pallas call: grid over (M/bm, N/bn)
    row/col tiles, each block carrying full K (the chunk's contraction
    dim) so every output element's K-sum happens in ONE dot.  Blocks
    default to the largest aligned tiles that fit VMEM."""
    from jax.experimental import pallas as pl
    from .quant import _auto_blocks

    M, K = a2.shape
    N = w.shape[1]
    auto_m, auto_n = _auto_blocks(M, K, N, 2 * a2.dtype.itemsize,
                                  2 * w.dtype.itemsize, 512, 512)
    bm, bn = block_m or auto_m, block_n or auto_n
    return pl.pallas_call(
        _agmm_chunk_kernel,
        grid=(M // bm, N // bn),
        in_specs=[pl.BlockSpec((bm, K), lambda i, j: (i, 0)),
                  pl.BlockSpec((K, bn), lambda i, j: (0, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        interpret=interpret,
    )(a2, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _pallas_chunk_matmul(a, w, block_m, block_n, interpret):
    """``a @ w`` with the forward tile-matmul in Pallas and the backward
    pinned to the XLA dot transposes the traced ``@`` would generate —
    pallas_call has no AD rule, and pinning keeps the ring_fused_pallas
    step's gradients on the same arithmetic as ring_fused's."""
    out, _ = _pcm_fwd(a, w, block_m, block_n, interpret)
    return out


def _pcm_fwd(a, w, block_m, block_n, interpret):
    lead = a.shape[:-1]
    a2 = a.reshape(-1, a.shape[-1])
    out_dtype = jnp.promote_types(a.dtype, w.dtype)
    out = _agmm_tile_call(a2, w, out_dtype, block_m, block_n, interpret)
    return out.reshape(*lead, w.shape[1]), (a, w)


def _pcm_bwd(block_m, block_n, interpret, res, g):
    a, w = res
    g2 = g.reshape(-1, g.shape[-1])
    a2 = a.reshape(-1, a.shape[-1])
    da = lax.dot_general(g2, w, (((1,), (1,)), ((), ())))
    dw = lax.dot_general(a2, g2, (((0,), (0,)), ((), ())))
    return da.reshape(a.shape).astype(a.dtype), dw.astype(w.dtype)


_pallas_chunk_matmul.defvjp(_pcm_fwd, _pcm_bwd)


def all_gather_matmul_pallas(a, w_shard, axis_name: str, *,
                             block_m: int | None = None,
                             block_n: int | None = None,
                             interpret: bool | None = None):
    """Kernel-tier :func:`all_gather_matmul`: the same ring choreography
    (shard hops stay ``lax.ppermute`` — the collective the contract
    counts and the ledger prices), with each per-chunk tile matmul
    running as a Pallas kernel instead of a traced ``@``.

    The ring hops are not in-kernel remote DMAs (interpret mode has no
    inter-device copy), so the decomposition point is the per-chunk
    matmul, tiled over M/N by ``block_m``/``block_n`` (default: the
    largest tiles that fit VMEM) with K never split.  Folding the hop
    itself into the kernel (``pltpu.make_async_remote_copy``
    double-buffered against the tile loop) is the recorded next step.

    AD: the ring scaffold stays plain traceable code (its transpose is
    the reversed-ring matmul-reduce-scatter, as for the XLA variant);
    only the chunk matmul carries a custom_vjp with XLA-dot backward."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n = axis_size(axis_name)
    if n == 1:   # degenerate ring: one whole-weight kernel call
        return _pallas_chunk_matmul(a, w_shard, block_m, block_n,
                                    interpret).astype(a.dtype)
    k_chunk = w_shard.shape[0]
    K = a.shape[-1]
    if K != n * k_chunk:
        raise ValueError(
            f"all_gather_matmul_pallas: activation contraction dim {K} "
            f"!= mesh axis {axis_name!r} size {n} x weight shard rows "
            f"{k_chunk} — the shard must be a 1/{n} row-slice of the "
            f"full weight (got shard shape {tuple(w_shard.shape)})")
    idx = lax.axis_index(axis_name)
    acc = jnp.zeros(a.shape[:-1] + (w_shard.shape[1],),
                    jnp.promote_types(a.dtype, w_shard.dtype))
    cur = w_shard
    for t in range(n):
        src = (idx - t) % n
        a_chunk = lax.dynamic_slice_in_dim(a, src * k_chunk, k_chunk,
                                           axis=a.ndim - 1)
        acc = acc + _pallas_chunk_matmul(a_chunk, cur, block_m, block_n,
                                         interpret)
        if t < n - 1:
            cur = lax.ppermute(cur, axis_name, _ring_perm(n))
    return acc.astype(a.dtype)


def matmul_reduce_scatter(a, b, axis_name: str, *, axis: int = 0):
    """Decomposed collective matmul, scatter side:
    ``psum_scatter(a @ b, axis)`` with each row-chunk's partial product
    computed right before its traveling accumulator needs it — partial
    products scatter as they finish instead of waiting for the full
    matmul then the full reduce-scatter.  Ring accumulation reassociates
    the device sum: numerically equivalent to the monolithic form, not
    bitwise."""
    n = axis_size(axis_name)
    if n == 1:
        return a @ b
    axis = axis % a.ndim
    if axis != 0:
        raise ValueError("matmul_reduce_scatter: only axis=0 (row chunks "
                         "of the result) is supported")
    _check_chunk("matmul_reduce_scatter", "result row dim", a.shape[0],
                 n, axis_name)
    idx = lax.axis_index(axis_name)
    chunk = a.shape[0] // n

    def partial_product(c):
        rows = lax.dynamic_slice_in_dim(a, c * chunk, chunk, axis=0)
        return rows @ b

    # accumulator for chunk (idx - s - 1) at step s lands fully summed on
    # its owner after n-1 hops (derivation: f(d, s) = d - s - 1 mod n)
    acc = partial_product((idx - 1) % n)
    for s in range(1, n):
        acc = lax.ppermute(acc, axis_name, _ring_perm(n))
        acc = acc + partial_product((idx - s - 1) % n)
    return acc


def barrier(axis_name: str):
    """Step-isolation barrier: a 1-element psum, exactly what
    ``dist.barrier`` is under NCCL (reference README.md:11-12,
    ``zero1.py:19-20``).  Returns the summed token; callers
    ``block_until_ready`` it for host-side isolation."""
    return lax.psum(jnp.ones((), dtype=jnp.float32), axis_name)


def tree_all_reduce(tree: Any, axis_name: str, *, mean: bool = True):
    """Per-leaf all_reduce of a pytree — the reference's per-param gradient
    all_reduce loop (``DDP/ddp.py:43-47``) as one tree_map.  One collective
    per leaf in the HLO, preserving trace-count parity."""
    return jax.tree.map(lambda g: all_reduce(g, axis_name, mean=mean), tree)


def tree_all_gather(tree: Any, axis_name: str, *, axis: int = 0,
                    tiled: bool = True):
    """Per-leaf all_gather of an arbitrarily nested pytree — the twin of
    the reference's recursive structured ``gather()``
    (``DDP/training_utils/utils.py:137-198``), which walks nested
    containers all-gathering every tensor.  Pytrees make the recursion a
    tree_map; non-array leaves pass through untouched, as the
    reference's non-tensor branches do; 0-d leaves gather into a
    (world_size,) vector (the reference stacks scalars the same way)."""
    def leaf(x):
        if not hasattr(x, "ndim"):
            return x
        if x.ndim == 0:
            return all_gather(x[None], axis_name, axis=0, tiled=True)
        return all_gather(x, axis_name, axis=axis, tiled=tiled)
    return jax.tree.map(leaf, tree)
