"""Pallas kernel tier: fp8 end-to-end (e4m3 fwd / e5m2 bwd per-tensor
scaling, dynamic / delayed / Pallas variants), the fused
all-gather-matmul kernel, the EQuARX quantized collectives generalized
to FSDP/TP traffic, and the paged-attention decode kernel — all pinned
on the 8-way simulated CPU mesh (``interpret=True`` tier).

Parity law of the tier: kernels that move data without changing the
per-element reduction order are BITWISE against their XLA reference
paths; quantized recipes are pinned to their documented error bounds.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from distributed_training_sandbox_tpu.models import transformer as T
from distributed_training_sandbox_tpu.ops import collectives as C
from distributed_training_sandbox_tpu.ops import quant as Q

pytestmark = pytest.mark.kernels

INTERP = jax.default_backend() != "tpu"


# ------------------------------------------------------- fp8 primitives

@pytest.fixture(scope="module")
def xw():
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 128), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), (128, 256), jnp.bfloat16)
    return x, w


def test_quantize_fp8_roundtrip(xw):
    x, _ = xw
    q, s = Q.quantize_fp8(x)
    assert q.dtype == Q.FP8_FWD_DTYPE and s.shape == ()
    back = q.astype(jnp.float32) * s
    # e4m3 keeps 3 mantissa bits: half-ulp relative error ≤ 2^-4 per
    # element in the normal range (per-tensor scale maps amax to 448)
    rel = float(jnp.mean(jnp.abs(back - x.astype(jnp.float32)))
                / jnp.mean(jnp.abs(x.astype(jnp.float32))))
    assert rel < 0.04
    # zero tensor: scale clamps to 1, codes to 0
    qz, sz = Q.quantize_fp8(jnp.zeros((4, 4)))
    assert float(jnp.max(jnp.abs(qz.astype(jnp.float32)))) == 0.0
    assert float(sz) == 1.0


def test_fp8_delayed_scaling_seeds_to_dynamic(xw):
    """The stateless CPU-tier instantiation seeds the amax history with
    the current tensor, so delayed == dynamic bitwise on first use."""
    x, _ = xw
    qd, sd = Q.quantize_fp8(x)
    qh, sh = Q.quantize_fp8(x, amax_history_len=16)
    np.testing.assert_array_equal(np.asarray(qd, np.float32),
                                  np.asarray(qh, np.float32))
    assert float(sd) == float(sh)
    # and the history helpers roll correctly: a larger past amax wins
    hist = Q.amax_history_update(jnp.zeros((4,)), x)
    assert float(hist[-1]) == float(jnp.max(jnp.abs(
        x.astype(jnp.float32))))
    spiked = hist.at[0].set(2 * float(hist[-1]))
    assert float(Q.scale_from_history(spiked, Q.FP8_FWD_DTYPE)) \
        > float(Q.scale_from_history(hist, Q.FP8_FWD_DTYPE))


def test_fp8_dense_close_to_bf16_and_bitwise_across_impls(xw):
    x, w = xw
    ref = (x.astype(jnp.float32) @ w.astype(jnp.float32))
    out = Q.fp8_dense(x, w)
    rel = float(jnp.mean(jnp.abs(out.astype(jnp.float32) - ref))
                / jnp.mean(jnp.abs(ref)))
    assert 0 < rel < 0.06
    # Pallas forward and delayed scaling are bitwise vs the XLA dynamic
    # path on CPU (same rounded operands, same f32 dot)
    outs = [Q.fp8_dense(x, w, impl="pallas", interpret=INTERP),
            Q.fp8_dense(x, w, amax_history_len=16)]
    for o in outs:
        np.testing.assert_array_equal(np.asarray(out, np.float32),
                                      np.asarray(o, np.float32))


def test_fp8_dense_backward_operand_roles(xw):
    """All three backward matmuls run on fp8 operands: grads agree with
    the exact bf16 backward loosely, and the Pallas impl's backward is
    bitwise vs the XLA impl's (both pin backward to XLA dots)."""
    x, w = xw

    def loss(fn):
        return lambda w: jnp.mean(fn(w).astype(jnp.float32) ** 2)

    ge = jax.grad(loss(lambda w: x @ w))(w)
    g8 = jax.grad(loss(lambda w: Q.fp8_dense(x, w)))(w)
    gp = jax.grad(loss(lambda w: Q.fp8_dense(
        x, w, impl="pallas", interpret=INTERP)))(w)
    rel = float(jnp.mean(jnp.abs(g8.astype(jnp.float32)
                                 - ge.astype(jnp.float32)))
                / jnp.mean(jnp.abs(ge.astype(jnp.float32))))
    assert 0 < rel < 0.10
    np.testing.assert_array_equal(np.asarray(g8, np.float32),
                                  np.asarray(gp, np.float32))


def test_resolve_quantized_dense_fp8_names(xw):
    x, w = xw
    base = Q.resolve_quantized_dense("fp8")(x, w)
    for name in ("fp8_delayed", "fp8_pallas"):
        out = Q.resolve_quantized_dense(name)(x, w)
        np.testing.assert_array_equal(np.asarray(base, np.float32),
                                      np.asarray(out, np.float32))
    with pytest.raises((KeyError, ValueError)):
        Q.resolve_quantized_dense("fp7")(x, w)


# --------------------------------------------- fsdp/tp step-level parity

@pytest.fixture(scope="module")
def train_fixture():
    from distributed_training_sandbox_tpu.parallel import fsdp

    cfg = T.TINY_LM
    # host copies: the donated steps delete device buffers they alias
    params = jax.tree.map(np.asarray,
                          T.init_params(jax.random.PRNGKey(0), cfg))
    batch = (
        jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                           cfg.vocab_size),
        jax.random.randint(jax.random.PRNGKey(2), (8, 32), 0,
                           cfg.vocab_size))
    return cfg, params, batch


def _fsdp_losses(mesh8, train_fixture, *, overlap="none", precision=None,
                 quantized_gather=False, quantized_grads=False, steps=3):
    from distributed_training_sandbox_tpu.parallel import fsdp

    cfg, params, batch = train_fixture
    mcfg = cfg if precision is None else dataclasses.replace(
        cfg, matmul_precision=precision)
    shards = fsdp.shard_params_fsdp(params, mesh8)
    opt = fsdp.init_fsdp_opt_state(shards)
    step = fsdp.make_fsdp_train_step(
        shards, mcfg, mesh8, overlap=overlap,
        quantized_gather=quantized_gather,
        quantized_grads=quantized_grads)
    losses = []
    for _ in range(steps):
        shards, opt, loss = step(shards, opt, batch)
        losses.append(float(loss))
    return losses


@pytest.fixture(scope="module")
def fsdp_bf16(mesh8, train_fixture):
    return _fsdp_losses(mesh8, train_fixture)


def test_fp8_fsdp_step_within_tolerance(mesh8, train_fixture, fsdp_bf16):
    """The pinned tolerance of the tentpole: fp8 losses within 5% of
    bf16 per step, and the three fp8 impls bitwise-identical to each
    other on CPU (the emulated dot upcasts identical rounded operands)."""
    fp8 = _fsdp_losses(mesh8, train_fixture, precision="fp8")
    fp8d = _fsdp_losses(mesh8, train_fixture, precision="fp8_delayed")
    fp8p = _fsdp_losses(mesh8, train_fixture, precision="fp8_pallas")
    assert fp8 == fp8d == fp8p, (fp8, fp8d, fp8p)
    for a, b in zip(fsdp_bf16, fp8):
        assert abs(a - b) / abs(a) < 0.05, (fsdp_bf16, fp8)
    assert all(np.isfinite(v) for v in fp8)


def test_ring_fused_pallas_bitwise_vs_ring_fused(mesh8, train_fixture):
    rf = _fsdp_losses(mesh8, train_fixture, overlap="ring_fused")
    rfp = _fsdp_losses(mesh8, train_fixture,
                       overlap="ring_fused_pallas")
    assert rf == rfp, (rf, rfp)


def test_quantized_grads_step_and_validation(mesh8, train_fixture,
                                             fsdp_bf16):
    from distributed_training_sandbox_tpu.parallel import fsdp

    qgg = _fsdp_losses(mesh8, train_fixture, quantized_gather=True,
                       quantized_grads=True)
    for a, b in zip(fsdp_bf16, qgg):
        assert abs(a - b) / abs(a) < 0.05, (fsdp_bf16, qgg)
    # quantized_grads rides the quantized gathers' backward: rejected
    # without them
    cfg, params, _ = train_fixture
    shards = fsdp.shard_params_fsdp(params, mesh8)
    with pytest.raises(ValueError, match="quantized_gather"):
        fsdp.make_fsdp_train_step(shards, cfg, mesh8,
                                  quantized_grads=True)


def test_tp_q8_rejoin_within_tolerance(train_fixture):
    from distributed_training_sandbox_tpu.parallel import fsdp, tensor

    cfg, params, batch = train_fixture
    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("dp", "tp"))

    def run(overlap):
        sh = tensor.shard_params_tp(params, mesh, "tp")
        op = fsdp.init_fsdp_opt_state(sh)
        st = tensor.make_tp_train_step(sh, cfg, mesh, overlap=overlap)
        out = []
        for _ in range(3):
            sh, op, loss = st(sh, op, batch)
            out.append(float(loss))
        return out

    base, q8 = run("none"), run("q8")
    for a, b in zip(base, q8):
        assert abs(a - b) / abs(a) < 0.05, (base, q8)


# ------------------------------------------- fused all-gather-matmul

def _assert_f32_dot_close(ref, out):
    """Two float32 programs that contract the same K products may sum
    them in different orders (XLA picks the dot emitter per program
    shape), so they agree to K·eps of the terms summed, not bitwise:
    1e-6 of the reference's largest entry is ~8 ulps there."""
    ref = np.asarray(ref)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=0,
                               atol=1e-6 * np.abs(ref).max())


def test_ag_matmul_pallas_bitwise(mesh8):
    """Whole-chunk Pallas blocks never split K: forward AND grads match
    the XLA path to float32 summation order, also when tiled over
    M/N."""
    a = jax.random.normal(jax.random.PRNGKey(3), (16, 64), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(4), (64, 48), jnp.float32)

    def run(fn, **kw):
        f = C.smap(lambda a, ws: fn(a, ws, "dp", **kw), mesh8,
                   (P(), P("dp")), P())
        out = jax.jit(f)(a, w)
        g = jax.jit(jax.grad(
            lambda a, ws: jnp.sum(C.smap(
                lambda a, ws: fn(a, ws, "dp", **kw), mesh8,
                (P(), P("dp")), P())(a, ws)), argnums=(0, 1)))(a, w)
        return out, g

    ref_out, ref_g = run(C.all_gather_matmul)
    for kw in ({"interpret": INTERP},
               {"interpret": INTERP, "block_m": 8, "block_n": 16}):
        out, g = run(C.all_gather_matmul_pallas, **kw)
        _assert_f32_dot_close(ref_out, out)
        for r, p in zip(jax.tree.leaves(ref_g), jax.tree.leaves(g)):
            _assert_f32_dot_close(r, p)


# ------------------------------------------- quantized collectives

def test_quantized_all_reduce_error_bound(mesh8):
    """Documented EQuARX bound: each rank's contribution carries at most
    half its quantum, so |qar - psum| ≤ n_ranks * max_scale / 2
    element-wise; backward is bitwise psum's."""
    x = jax.random.normal(jax.random.PRNGKey(5), (64, 128), jnp.float32)

    def compare(xs):
        exact = jax.lax.psum(xs, "dp")
        approx = Q.quantized_all_reduce(xs, "dp")
        _, s = Q.quantize_int8(xs, axis=-1)
        bound = C.axis_size("dp") * jax.lax.pmax(
            jnp.max(s), "dp") / 2.0
        return exact, approx, bound

    exact, approx, bound = jax.jit(
        C.smap(compare, mesh8, P("dp"), (P(), P(), P())))(x)
    err = float(jnp.max(jnp.abs(exact - approx)))
    assert 0 < err <= float(bound), (err, float(bound))

    gq = jax.jit(C.smap(jax.grad(
        lambda xs: jnp.sum(Q.quantized_all_reduce(xs, "dp"))),
        mesh8, P("dp"), P("dp")))(x)
    gp = jax.jit(C.smap(jax.grad(
        lambda xs: jnp.sum(jax.lax.psum(xs, "dp"))),
        mesh8, P("dp"), P("dp")))(x)
    np.testing.assert_array_equal(np.asarray(gq), np.asarray(gp))


def test_quantized_reduce_scatter_error_bound(mesh8):
    x = jax.random.normal(jax.random.PRNGKey(6), (64, 128), jnp.float32)

    def compare(xs):
        exact = jax.lax.psum_scatter(xs, "dp", scatter_dimension=0,
                                     tiled=True)
        approx = Q.quantized_reduce_scatter(xs, "dp", axis=0)
        _, s = Q.quantize_int8(xs, axis=-1)
        bound = C.axis_size("dp") * jax.lax.pmax(
            jnp.max(s), "dp") / 2.0
        return exact, approx, bound

    exact, approx, bound = jax.jit(C.smap(
        compare, mesh8, P("dp"), (P("dp"), P("dp"), P())))(x)
    err = float(jnp.max(jnp.abs(exact - approx)))
    assert 0 < err <= float(bound), (err, float(bound))
    # backward pinned to the monolithic reduce-scatter's transpose
    gq = jax.jit(C.smap(jax.grad(
        lambda xs: jnp.sum(Q.quantized_reduce_scatter(xs, "dp", 0))),
        mesh8, P("dp"), P("dp")))(x)
    gp = jax.jit(C.smap(jax.grad(
        lambda xs: jnp.sum(jax.lax.psum_scatter(
            xs, "dp", scatter_dimension=0, tiled=True))),
        mesh8, P("dp"), P("dp")))(x)
    np.testing.assert_array_equal(np.asarray(gq), np.asarray(gp))


# ------------------------------------------- paged-attention kernel

@pytest.mark.parametrize("kv_quant", [False, True])
@pytest.mark.parametrize("use_mesh", [False, True])
def test_paged_decode_kernel_bitwise(kv_quant, use_mesh):
    """The in-place page-table kernel against the gather-based reference
    layer body, float and int8-KV pools, with and without a TP mesh:
    every emitted token identical; every KV pool buffer identical for
    int8 pools (integer accumulation associates) and equal to float32
    summation order for float pools."""
    from distributed_training_sandbox_tpu.models.generate import (
        _decode_cfg)
    from distributed_training_sandbox_tpu.serving import (
        PagedKVPool, make_serve_decode_step)
    from distributed_training_sandbox_tpu.utils import make_mesh

    mcfg = T.TINY_LM
    B, page_size, pages_per = 4, 8, 4
    params = T.init_params(jax.random.PRNGKey(0), mcfg)

    def run(paged_kernel, steps=4):
        mesh = make_mesh({"dp": 4, "tp": 2}, register=False) \
            if use_mesh else None
        p = params
        if use_mesh:
            from distributed_training_sandbox_tpu.parallel import tensor
            p = tensor.shard_params_tp(params, mesh, "tp")
        pool = PagedKVPool(_decode_cfg(mcfg), B * pages_per + 1,
                           page_size, kv_quant=kv_quant, mesh=mesh)
        step = make_serve_decode_step(
            mcfg, p, mesh=mesh,
            pool_spec=pool.spec if use_mesh else None,
            paged_kernel=paged_kernel)
        pages = jnp.asarray(np.arange(1, B * pages_per + 1,
                                      dtype=np.int32).reshape(
                                          B, pages_per))
        bufs = pool.bufs
        toks = jnp.array([5, 17, 40, 3], jnp.int32)
        lengths = jnp.zeros((B,), jnp.int32)
        stop_at = jnp.full((B,), page_size * pages_per - 1, jnp.int32)
        active = jnp.ones((B,), bool)
        out = []
        for _ in range(steps):
            toks, lengths, active, bufs, _ = step(
                bufs, p, pages, toks, lengths, stop_at, active)
            out.append(np.asarray(toks))
        return np.stack(out), jax.tree.map(np.asarray, bufs)

    t_ref, b_ref = run(False)
    t_k, b_k = run(True)
    np.testing.assert_array_equal(t_ref, t_k)
    for a, b in zip(jax.tree.leaves(b_ref), jax.tree.leaves(b_k)):
        if kv_quant:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        else:
            _assert_f32_dot_close(a, b)


def test_paged_attention_rejects_multi_token():
    from distributed_training_sandbox_tpu.ops.paged_attention import (
        paged_attention_decode)

    qg = jnp.zeros((2, 2, 1, 4, 8))           # S=2
    pk = jnp.zeros((8, 4, 1, 8))
    pages = jnp.zeros((2, 2), jnp.int32)
    apos = jnp.zeros((2, 1), jnp.int32)
    with pytest.raises(ValueError, match="decode"):
        paged_attention_decode(qg, pk, pk, pages, apos)


# ----------------------------- the float decode kernel, on its own

# name -> (page, n_kv, rep, hd, pages a slot, dtype): tiny widths, a
# table longer than one DMA block that is no multiple of it, one of three
# blocks, the serving cells' geometry, and the looped model's call (group
# size 1, a 40-page table: three blocks)
_DECODE_GEOMETRY = {
    "tiny": (4, 2, 2, 8, 3, jnp.float32),
    "tiny-blocks": (4, 1, 4, 8, 19, jnp.float32),
    "tiny-3-blocks": (4, 2, 2, 8, 40, jnp.float32),
    "cell": (16, 4, 4, 128, 20, jnp.float32),
    "loop-cell": (16, 16, 1, 128, 40, jnp.float32),
}


def _decode_case(geometry, seed=0, lens=None):
    """A pool, a page table and ragged lengths for ``geometry``: an
    inactive slot (0), 1, a page boundary and its neighbours, a DMA
    block boundary and its neighbours where the table has one, and the
    full view; or the lengths given."""
    from distributed_training_sandbox_tpu.ops.paged_attention import (
        PAGES_PER_BLOCK)
    page, nkv, rep, hd, P, dt = _DECODE_GEOMETRY[geometry]
    V, span = P * page, PAGES_PER_BLOCK * page
    if lens is None:
        lens = [0, 1, page - 1, page, page + 1, V - 1, V]
        if span < V:
            lens += [span - 1, span, span + 1]
    lens = np.asarray(lens, np.int32)
    B = len(lens)
    n_pages = B * P + 1
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    pk = jax.random.normal(ks[0], (n_pages, page, nkv, hd), dt)
    pv = jax.random.normal(ks[1], (n_pages, page, nkv, hd), dt)
    qg = jax.random.normal(ks[2], (B, 1, nkv, rep, hd), dt)
    pages = np.random.RandomState(seed).permutation(
        np.arange(1, n_pages)).reshape(B, P).astype(np.int32)
    apos = jnp.asarray(np.maximum(lens - 1, 0)[:, None])
    valid = jnp.asarray((lens > 0)[:, None])
    return qg, pk, pv, jnp.asarray(pages), apos, valid, lens


def _planted(pools, pages, lens, value, whole_pages, los=0):
    """``pools`` with ``value`` planted where no slot may look, the null
    page included: in every position past a slot's length (its last live
    page's tail among them), or with ``whole_pages`` in every table entry
    that ``pages_copied`` says the kernel does not copy (past the last
    live page and, under a lower bound ``los``, before the page that
    holds it) and in those alone."""
    from distributed_training_sandbox_tpu.ops.paged_attention import (
        pages_copied)
    n_pages, page = pools[0].shape[:2]
    P = pages.shape[1]
    # position v of slot b lives at (pages[b, v // page], v % page)
    if whole_pages:
        first = (np.zeros_like(lens) + los)[:, None] // page
        entry = np.arange(P)[None, :]
        copied = (entry >= first) & (
            entry < first + pages_copied(lens, page, los)[:, None])
        past = np.repeat(~copied, page, axis=1)
    else:
        past = np.arange(P * page)[None, :] >= lens[:, None]   # (B, V)
    junk = np.zeros((n_pages, page), bool)
    junk[np.asarray(pages), :] = past.reshape(len(lens), P, page)
    junk[0] = True
    return [jnp.where(jnp.asarray(junk)[:, :, None, None], value, pool)
            for pool in pools]


@pytest.mark.parametrize("geometry", list(_DECODE_GEOMETRY))
def test_paged_decode_kernel_matches_gather(geometry):
    """The float kernel (interpret mode) against the engine's
    gather-then-einsum attention core, to float32 summation order, at
    every ragged length; an inactive slot gets zeros."""
    from chip_smoke import paged_attention_xla
    from distributed_training_sandbox_tpu.ops.paged_attention import (
        paged_attention_decode)

    qg, pk, pv, pages, apos, valid, lens = _decode_case(geometry)
    out = paged_attention_decode(qg, pk, pv, pages, apos, valid=valid)
    ref = paged_attention_xla(qg, pk, pv, pages, apos, qg.dtype)
    assert out.shape == ref.shape and out.dtype == jnp.float32
    live = lens > 0
    _assert_f32_dot_close(np.asarray(ref)[live], np.asarray(out)[live])
    assert not np.asarray(out)[~live].any()
    # without ``valid`` every slot is live at apos + 1
    every = paged_attention_decode(qg, pk, pv, pages, apos)
    _assert_f32_dot_close(ref, every)


@pytest.mark.parametrize("geometry", list(_DECODE_GEOMETRY))
def test_paged_decode_kernel_ignores_what_lies_past_a_length(geometry):
    """Finite garbage in a slot's pages past its length, in its unused
    pages and in the null page changes no bit of the output."""
    from distributed_training_sandbox_tpu.ops.paged_attention import (
        paged_attention_decode)

    qg, pk, pv, pages, apos, valid, lens = _decode_case(geometry, seed=1)
    clean = paged_attention_decode(qg, pk, pv, pages, apos, valid=valid)
    noise = 1e4 * jax.random.normal(jax.random.PRNGKey(9), pk.shape,
                                    pk.dtype)
    out = paged_attention_decode(
        qg, *_planted((pk, pv), pages, lens, noise, whole_pages=False),
        pages, apos, valid=valid)
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(out))


@pytest.mark.parametrize("geometry", list(_DECODE_GEOMETRY))
def test_paged_decode_kernel_copies_no_page_past_a_length(geometry):
    """NaN in every page the schedule (``pages_copied``) leaves out (a
    slot's table entries past its last live page, whole unused tables,
    the null page) changes no bit of the output: a page copied after all
    brings its NaN to ``0.0 * NaN`` in ``p @ V``, and so does a row of the
    buffer that no copy of this call wrote and nothing zeroed (interpret
    mode hands the kernel its scratch full of NaN)."""
    from distributed_training_sandbox_tpu.ops.paged_attention import (
        paged_attention_decode)

    qg, pk, pv, pages, apos, valid, lens = _decode_case(geometry, seed=2)
    clean = np.asarray(
        paged_attention_decode(qg, pk, pv, pages, apos, valid=valid))
    assert np.isfinite(clean).all()
    out = paged_attention_decode(
        qg, *_planted((pk, pv), pages, lens, jnp.nan, whole_pages=True),
        pages, apos, valid=valid)
    np.testing.assert_array_equal(clean, np.asarray(out))


# slots of 1, 2 and 3 blocks (in blocks; 0: an empty slot), so that the
# half in flight at a hand-over alternates, with empty slots between
# live ones, first and last
_HANDOVERS = {
    "interleaved-1-3": [0, 1, 0, 0, 3, 0],
    "interleaved-2-1": [0, 2, 0, 0, 1, 0],
    "interleaved-3-2": [0, 3, 0, 0, 2, 0],
    "every-parity": [1, 1, 2, 2, 3, 3, 1, 3, 2, 1],
    "all-empty": [0, 0, 0, 0, 0, 0],
}


@pytest.mark.parametrize("blocks", list(_HANDOVERS))
@pytest.mark.parametrize("geometry", ["tiny-3-blocks", "loop-cell"])
def test_paged_decode_kernel_hands_its_copies_from_slot_to_slot(geometry,
                                                                blocks):
    """A slot's first block is started while its predecessor's last one
    is multiplied, over empty slots too: against the gather reference
    with live slots of one, two and three blocks in every order of the
    two halves, empty slots first, last and between, and a call whose
    every slot is empty (zeros, nothing started, nothing waited for)."""
    from chip_smoke import paged_attention_xla
    from distributed_training_sandbox_tpu.ops.paged_attention import (
        PAGES_PER_BLOCK, paged_attention_decode)

    page, P = _DECODE_GEOMETRY[geometry][0], _DECODE_GEOMETRY[geometry][4]
    span = PAGES_PER_BLOCK * page
    # n blocks: a length that ends 3 positions into the n-th, or the table
    lens = [min((n - 1) * span + 3 + 5 * i, P * page) if n else 0
            for i, n in enumerate(_HANDOVERS[blocks])]
    qg, pk, pv, pages, apos, valid, lens = _decode_case(
        geometry, seed=3, lens=lens)
    out = np.asarray(paged_attention_decode(
        qg, *_planted((pk, pv), pages, lens, jnp.nan, whole_pages=True),
        pages, apos, valid=valid))
    ref = np.asarray(paged_attention_xla(qg, pk, pv, pages, apos, qg.dtype))
    live = lens > 0
    if live.any():
        _assert_f32_dot_close(ref[live], out[live])
    assert not out[~live].any()


# the window variant's lower bounds, in positions of "tiny-3-blocks"
# (blocks of 64: 0-63, 64-127, 128-159), one a live slot
_LOWER_BOUNDS = {
    "inside-the-first-block": [5, 17, 40],
    "at-a-block-edge": [64, 128, 64],
    "past-the-first-block": [70, 130, 100],
    "mixed": [0, 129, 63],
}


@pytest.mark.parametrize("bounds", list(_LOWER_BOUNDS))
def test_paged_decode_kernel_bounded_starts_at_the_lower_bounds_block(bounds):
    """``lo``: the first block copied is the one that holds it, for the
    slot a predecessor prefetches for as for the call's first; pages
    wholly under it are not copied (NaN there changes nothing), positions
    under it in its page are masked: against the engine's gather core
    with the same bound, empty slots between."""
    from chip_smoke import paged_attention_xla
    from distributed_training_sandbox_tpu.ops.paged_attention import (
        paged_attention_decode)

    lens = np.asarray([0, 150, 0, 160, 131, 0], np.int32)
    los = np.zeros_like(lens)
    los[lens > 0] = _LOWER_BOUNDS[bounds]
    qg, pk, pv, pages, apos, valid, lens = _decode_case(
        "tiny-3-blocks", seed=4, lens=lens)
    lo = jnp.asarray(los[:, None])
    ref = np.asarray(paged_attention_xla(qg, pk, pv, pages, apos, qg.dtype,
                                         lo=lo))
    out = np.asarray(paged_attention_decode(
        qg, *_planted((pk, pv), pages, lens, jnp.nan, whole_pages=True,
                      los=los),
        pages, apos, valid=valid, lo=lo))
    live = lens > 0
    _assert_f32_dot_close(ref[live], out[live])
    assert not out[~live].any()


@pytest.mark.parametrize("geometry", ["tiny-blocks", "cell", "loop-cell"])
def test_pages_copied_is_the_kernels_schedule_counted_block_by_block(
        geometry):
    """``pages_copied`` against a brute-force walk of the kernel's loops
    (block by block, page by page, the guard it puts round a copy) at
    every length the table holds, and with a lower bound at every page
    edge and one position either side of it."""
    from distributed_training_sandbox_tpu.ops.paged_attention import (
        PAGES_PER_BLOCK, pages_copied)

    page, _, _, _, P, _ = _DECODE_GEOMETRY[geometry]
    bp = min(PAGES_PER_BLOCK, P)

    def walked(length, lo=0):
        first, end = lo // page, min(-(-length // page), P)
        return sum(first <= blk * bp + i < end
                   for blk in range(first // bp, -(-end // bp))
                   for i in range(bp))

    lengths = np.arange(P * page + 1)
    assert pages_copied(lengths, page).tolist() == [
        walked(n) for n in lengths]
    assert pages_copied(0, page) == 0 and pages_copied(1, page) == 1
    los = sorted({max(e + d, 0) for e in range(0, P * page, page)
                  for d in (-1, 0, 1)})
    for n in (P * page, P * page - page - 1, 2 * page + 1):
        assert pages_copied(n, page, np.asarray(los)).tolist() == [
            walked(n, lo) for lo in los]


# ----------------------------- the flash prefill kernel, on its own

# name -> (page, n_kv, rep, hd, pages a slot, chunk, dtype; the chunk
# divides the view, as the engine's does): tiny widths in one DMA block,
# a table longer than one block that is no multiple of it, and the two
# serving cells' geometry (chat: chunk 256 against a
# 2,048 view; documents: chunk 512 against 8,192)
_PREFILL_GEOMETRY = {
    "tiny": (4, 2, 2, 8, 3, 4, jnp.float32),
    "tiny-blocks": (4, 1, 4, 8, 19, 4, jnp.float32),
    "chat-cell": (16, 4, 4, 128, 128, 256, jnp.float32),
    "documents-cell": (16, 4, 4, 128, 512, 512, jnp.float32),
}


def _prefill_case(geometry, seed=0):
    """A pool, a page table and one chunk a batch row for ``geometry``,
    rows ragged in (chunk start, prompt length): a pad row (length 0), a
    prompt shorter than a page, prompts that end at a page boundary and
    at a DMA block boundary and their neighbours (each one's final
    chunk, so rows past the prompt are padding), a first chunk and a
    mid-prompt chunk of a longer prompt, and the full view."""
    from distributed_training_sandbox_tpu.ops.flash_prefill import (
        PAGES_PER_BLOCK)
    page, nkv, rep, hd, P, S, dt = _PREFILL_GEOMETRY[geometry]
    V, span = P * page, PAGES_PER_BLOCK * page
    plens = [0, page - 1, page, page + 1, V]
    if span < V:
        plens += [span - 1, span, span + 1]
    if geometry == "documents-cell":      # a 268 MB reference a row
        plens = [0, page - 1, span + 1, V]
    final = lambda n: max(n - 1, 0) // S * S      # its last chunk's start
    rows = [(final(n), n) for n in plens]
    if V >= 3 * S:
        rows += [(0, V - 3), (S, V - 3)]
    starts, plens = (np.asarray(c, np.int32) for c in zip(*rows))
    B = len(rows)
    n_pages = B * P + 1
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    pk = jax.random.normal(ks[0], (n_pages, page, nkv, hd), dt)
    pv = jax.random.normal(ks[1], (n_pages, page, nkv, hd), dt)
    qg = jax.random.normal(ks[2], (B, S, nkv, rep, hd), dt)
    pages = np.random.RandomState(seed).permutation(
        np.arange(1, n_pages)).reshape(B, P).astype(np.int32)
    apos = starts[:, None] + np.arange(S, dtype=np.int32)[None]
    valid = apos < plens[:, None]
    return (qg, pk, pv, jnp.asarray(pages), jnp.asarray(apos),
            jnp.asarray(valid))


def _prefill_reference(qg, pk, pv, pages, apos):
    """The engine's gather-then-einsum attention core, a batch row at a
    time (its float32 scores are 268 MB a row at the documents cell)."""
    from chip_smoke import paged_attention_xla
    row = jax.jit(lambda q, pg, ap: paged_attention_xla(
        q, pk, pv, pg, ap, q.dtype))
    return np.concatenate([
        np.asarray(row(qg[b:b + 1], pages[b:b + 1], apos[b:b + 1]))
        for b in range(qg.shape[0])])


@pytest.mark.parametrize("geometry", list(_PREFILL_GEOMETRY))
def test_flash_prefill_kernel_matches_gather(geometry):
    """The kernel (interpret mode) against the engine's gather path, to
    float32 summation order, at every valid row of every ragged chunk; a
    batch row with nothing live gets zeros, and padding rows are
    finite."""
    from distributed_training_sandbox_tpu.ops.flash_prefill import (
        paged_flash_prefill)

    qg, pk, pv, pages, apos, valid = _prefill_case(geometry)
    out = paged_flash_prefill(qg, pk, pv, pages, apos, valid=valid)
    ref = _prefill_reference(qg, pk, pv, pages, apos)
    assert out.shape == ref.shape and out.dtype == jnp.float32
    out, valid = np.asarray(out), np.asarray(valid)
    assert valid[1:].any(axis=1).all() and not valid[0].any()
    _assert_f32_dot_close(ref[valid], out[valid])
    assert not out[0].any() and np.isfinite(out).all()
    if geometry.startswith("tiny"):
        # without ``valid`` every row is live: the chunk's own last
        # position bounds the read
        every = paged_flash_prefill(qg, pk, pv, pages, apos)
        _assert_f32_dot_close(ref, every)


@pytest.mark.parametrize("geometry", ["tiny", "tiny-blocks", "chat-cell"])
def test_flash_prefill_kernel_ignores_what_lies_past_the_live_end(geometry):
    """Finite garbage in a slot's pages past its chunk's live end (where
    the engine would have diverted the padding rows' K/V, and whatever an
    earlier request left), in its unused pages and the null page, and in
    the padding rows' queries changes no bit of a valid row's output."""
    from distributed_training_sandbox_tpu.ops.flash_prefill import (
        paged_flash_prefill)

    qg, pk, pv, pages, apos, valid = _prefill_case(geometry, seed=1)
    page, P = pk.shape[1], pages.shape[1]
    clean = paged_flash_prefill(qg, pk, pv, pages, apos, valid=valid)
    v = np.asarray(valid)
    ends = np.where(v.any(1), (np.asarray(apos) * v).max(1) + 1, 0)
    # position w of row b lives at (pages[b, w // page], w % page)
    past = np.arange(P * page)[None, :] >= ends[:, None]       # (B, V)
    junk = np.zeros(pk.shape[:2], bool)
    junk[np.asarray(pages), :] = past.reshape(len(ends), P, page)
    junk[0] = True
    noise = 1e4 * jax.random.normal(jax.random.PRNGKey(9), pk.shape,
                                    pk.dtype)
    dirty = lambda pool: jnp.where(jnp.asarray(junk)[:, :, None, None],
                                   noise, pool)
    q_dirty = jnp.where(valid[:, :, None, None, None], qg, 1e4)
    out = paged_flash_prefill(q_dirty, dirty(pk), dirty(pv), pages, apos,
                              valid=valid)
    np.testing.assert_array_equal(np.asarray(clean)[v], np.asarray(out)[v])
    assert not np.asarray(out)[0].any()


@pytest.mark.parametrize("dtype,head_dim,page,chunk,takes", [
    (jnp.bfloat16, 128, 16, 512, True),     # the documents cell
    (jnp.bfloat16, 128, 16, 256, True),     # the chat cell
    (jnp.bfloat16, 128, 16, 16, True),
    (jnp.float32, 128, 8, 8, True),
    (jnp.bfloat16, 256, 32, 128, True),
    (jnp.bfloat16, 128, 16, 8, False),      # half a bf16 sublane tile
    (jnp.bfloat16, 128, 16, 5, False),      # speculative verify's k + 1
    (jnp.bfloat16, 128, 16, 1, False),      # a decode step
    (jnp.bfloat16, 64, 16, 256, False),     # head_dim under a lane tile
    (jnp.bfloat16, 128, 8, 256, False),     # half a page tile
    (jnp.int8, 128, 32, 256, False),
], ids=lambda v: getattr(v, "__name__", str(v)))
def test_prefill_kernel_takes(dtype, head_dim, page, chunk, takes):
    from distributed_training_sandbox_tpu.ops.flash_prefill import (
        prefill_kernel_takes)
    assert prefill_kernel_takes(dtype, head_dim, page, chunk) is takes


# --------------------------- the gated delta rule's decode-step kernel

#: name -> (slots, heads, key dim, value dim): the tests' rehearsal dims
#: (a group is all three heads and ends inside a lane tile), sixteen
#: slots (two row blocks) and twelve (the rows padded up to two), and
#: slots of the published dims (head pairs of 384 lanes, fifteen to a slot)
_STEP_GEOMETRY = {"rehearsal": (5, 3, 8, 16), "two-row-blocks": (16, 3, 8, 16),
                  "a-block-and-a-half": (12, 3, 8, 16),
                  "published-slot": (2, 30, 96, 192)}
_STEP_LIVE = {"all": lambda B: np.ones(B, bool),
              "some": lambda B: np.arange(B) % 3 == 1,
              "last-only": lambda B: np.arange(B) == B - 1,
              "none": lambda B: np.zeros(B, bool)}


def _step_inputs(seed, B, n, dk, dv, live, steps=1):
    """What ``gdn_hybrid.linear_inputs`` hands a decode step: unit k, q
    scaled, g < 0 and beta in (0, 2) where ``live``, both 0 elsewhere; and
    a NON-zero stored state (B, dk, n dv)."""
    ks = jax.random.split(jax.random.key(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (steps, B, n, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (steps, B, n, dk)))
    v = jax.random.normal(ks[2], (steps, B, n, dv))
    g = -jax.random.uniform(ks[3], (steps, B, n), minval=0.01, maxval=2.0)
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (steps, B, n)))
    keep = jnp.asarray(live)[None, :, None]
    return (q, k, v, jnp.where(keep, g, 0.0), jnp.where(keep, beta, 0.0),
            jax.random.normal(ks[5], (B, dk, n * dv)))


@pytest.mark.parametrize("live", list(_STEP_LIVE))
@pytest.mark.parametrize("geometry", list(_STEP_GEOMETRY))
def test_gdn_step_kernel_is_the_xla_step_on_live_slots(geometry, live):
    """The Pallas step kernel against ``gdn_hybrid.recurrent_step``'s XLA
    form from a non-zero state: ``o`` and the new state of a live slot to
    float32 summation order (the kernel sums a head's 8 or 96 products in
    another order), a dead slot's state bit for bit what it was and its
    ``o`` zero."""
    from distributed_training_sandbox_tpu.models import gdn_hybrid as G
    from distributed_training_sandbox_tpu.ops.gdn_step import gdn_decode_step
    B, n, dk, dv = _STEP_GEOMETRY[geometry]
    mask = _STEP_LIVE[live](B)
    q, k, v, g, beta, s0 = _step_inputs(7, B, n, dk, dv, mask)
    args = (q[0], k[0], v[0], g[0], beta[0], s0)
    o_want, s_want = G.recurrent_step(*args)
    o, s = gdn_decode_step(*args, interpret=INTERP)
    assert o.shape == (B, n, dv) and s.shape == s0.shape
    np.testing.assert_allclose(
        o, jnp.where(jnp.asarray(mask)[:, None, None], o_want, 0.0),
        atol=2e-6)
    np.testing.assert_allclose(s, s_want, atol=2e-6)
    np.testing.assert_array_equal(np.asarray(s)[~mask],
                                  np.asarray(s0)[~mask])
    if mask.any():
        assert not np.array_equal(np.asarray(s)[mask], np.asarray(s0)[mask])


def test_gdn_step_kernel_chained_stays_inside_float32_rounding():
    """Eight steps chained through the kernel and through the XLA form,
    slots of the published dims of which one is live throughout, one
    joins at the fifth step and one never: outputs and states stay within
    float32 rounding of each other (states of size ~1)."""
    from distributed_training_sandbox_tpu.models import gdn_hybrid as G
    from distributed_training_sandbox_tpu.ops.gdn_step import gdn_decode_step
    B, n, dk, dv = 3, 30, 96, 192
    q, k, v, g, beta, s0 = _step_inputs(11, B, n, dk, dv, np.ones(B, bool),
                                        steps=8)
    late = (jnp.arange(8) >= 4)[:, None]
    on = jnp.stack([jnp.ones((8, 1), bool), late, jnp.zeros((8, 1), bool)],
                   axis=1)                                  # (8, B, 1)
    g, beta = jnp.where(on, g, 0.0), jnp.where(on, beta, 0.0)
    s_k = s_x = s0
    for t in range(8):
        o_x, s_x = G.recurrent_step(q[t], k[t], v[t], g[t], beta[t], s_x)
        o_k, s_k = gdn_decode_step(q[t], k[t], v[t], g[t], beta[t], s_k,
                                   interpret=INTERP)
        np.testing.assert_allclose(o_k, jnp.where(on[t][..., None], o_x, 0),
                                   atol=2e-6)
    np.testing.assert_allclose(s_k, s_x, atol=4e-6)
    np.testing.assert_array_equal(s_k[2], s0[2])        # never live
    assert not np.array_equal(np.asarray(s_k[1]), np.asarray(s0[1]))


@pytest.mark.parametrize("live", [
    [0, 0, 0, 0, 0, 0, 0, 0], [1, 1, 1, 1, 1, 1, 1, 1],
    [0, 0, 1, 0, 1, 1, 0, 0], [0, 0, 0, 0, 0, 0, 0, 1],
    [1, 0, 0, 0, 0, 0, 0, 0]], ids=lambda v: "".join(map(str, v)))
def test_gdn_step_kernel_never_writes_a_dead_slot(live):
    """What the kernel's grid visits, and what its pipeline writes back.
    ``visit_list`` names a live slot's own block at its step and, at a
    dead slot's step, the block the pipeline already holds (the last live
    slot's; before the first live slot, that one's), so no dead slot is
    ever a block of the grid; and under Pallas' TPU interpreter, whose
    buffers start as NaN and which copies a block back only when the grid
    moves off it, every state comes back whole: live ones updated, dead
    ones bit for bit, also when NOTHING is live and the one block the
    grid holds is written back untouched."""
    from jax.experimental.pallas import tpu as pltpu
    from distributed_training_sandbox_tpu.models import gdn_hybrid as G
    from distributed_training_sandbox_tpu.ops import gdn_step as K
    mask = np.asarray(live, bool)
    src = np.asarray(K.visit_list(jnp.asarray(mask)))
    if mask.any():
        assert set(src) <= set(np.nonzero(mask)[0])
        assert (src[mask] == np.nonzero(mask)[0]).all()
        assert (np.diff(src) >= 0).all()        # a block is never revisited
    else:
        assert not src.any()
    B, n, dk, dv = 8, 3, 8, 16
    q, k, v, g, beta, s0 = _step_inputs(13, B, n, dk, dv, mask)
    _, s_want = G.recurrent_step(q[0], k[0], v[0], g[0], beta[0], s0)
    kq = jnp.concatenate([k[0], q[0]], axis=1).transpose(0, 2, 1)
    rows = jnp.stack([jnp.repeat(jnp.exp(g[0]), dv, axis=-1),
                      jnp.repeat(beta[0], dv, axis=-1),
                      v[0].reshape(B, n * dv)])
    o, s = K._step(jnp.asarray(src), jnp.asarray(mask, jnp.int32), kq, rows,
                   s0, n=n, interpret=pltpu.InterpretParams())
    assert not np.isnan(np.asarray(s)).any()
    assert not np.isnan(np.asarray(o)).any()
    np.testing.assert_allclose(s, s_want, atol=2e-6)
    np.testing.assert_array_equal(np.asarray(s)[~mask],
                                  np.asarray(s0)[~mask])


@pytest.mark.parametrize("n,dk,dv,takes", [
    (30, 96, 192, True),        # the hybrid cell: head pairs of 384 lanes
    (16, 128, 128, True),       # a head is a lane tile
    (4, 64, 64, True),          # pairs of heads
    (6, 8, 320, True),          # pairs of heads, five tiles
    (15, 96, 192, False),       # an odd head count: no whole pairs
    (30, 100, 192, False),      # the key dim is no whole sublane tiles
    (3, 8, 16, False),          # the tests' dims: interpret mode only
])
def test_gdn_step_kernel_takes(n, dk, dv, takes):
    from distributed_training_sandbox_tpu.ops.gdn_step import (
        head_group, step_kernel_takes)
    assert step_kernel_takes(n, dk, dv) is takes
    if takes:
        assert (head_group(n, dv) * dv) % 128 == 0 and n % head_group(n, dv) == 0


# ------------------------------------------- what a TPU makes of them

def test_serving_kernels_refuse_a_tpu(monkeypatch):
    """The int8 decode kernel does not lower on a TPU.  There it raises
    an error that names Pallas' refusal — whatever ``interpret`` says, so
    it can neither run interpreted nor give way to the gather path
    unseen.  The float decode kernel and, since PR 27, the flash prefill
    kernel do not refuse: they compile there at the shapes
    ``decode_kernel_takes`` / ``prefill_kernel_takes`` name and say so
    at the others."""
    from distributed_training_sandbox_tpu.ops.flash_prefill import (
        paged_flash_prefill, prefill_kernel_takes)
    from distributed_training_sandbox_tpu.ops.paged_attention import (
        decode_kernel_takes, paged_attention_decode)

    pk = jnp.zeros((8, 4, 1, 8))
    pk8 = jnp.zeros((8, 4, 1, 8), jnp.int8)
    scale = jnp.ones((8, 4, 1, 1))
    pages = jnp.zeros((2, 2), jnp.int32)
    q8 = jnp.zeros((2, 1, 1, 4, 8), jnp.int8)
    kw8 = dict(q_scale=jnp.ones((2, 1, 1, 4, 1)), pk_s=scale, pv_s=scale)
    q = jnp.zeros((2, 1, 1, 4, 8))
    q_pre = jnp.zeros((2, 4, 1, 4, 8))
    apos = jnp.zeros((2, 1), jnp.int32)
    apos_pre = jnp.zeros((2, 4), jnp.int32)
    # fine where there is no TPU
    assert paged_attention_decode(q8, pk8, pk8, pages, apos,
                                  **kw8).shape == q8.shape
    assert paged_attention_decode(q, pk, pk, pages, apos).shape == q.shape
    assert paged_flash_prefill(q_pre, pk, pk, pages,
                               apos_pre).shape == q_pre.shape
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for how in ({}, {"interpret": True}, {"interpret": False}):
        with pytest.raises(NotImplementedError,
                           match="dynamic_update_slice") as e:
            paged_attention_decode(q8, pk8, pk8, pages, apos, **kw8, **how)
        assert "paged_attention_decode" in str(e.value)
        assert "ROADMAP" not in str(e.value)
    # the float kernels: a shape one does not compile for is named
    assert not decode_kernel_takes(pk.dtype, 8, 4)
    with pytest.raises(ValueError, match="decode_kernel_takes"):
        paged_attention_decode(q, pk, pk, pages, apos)
    assert not prefill_kernel_takes(pk.dtype, 8, 4, 4)
    with pytest.raises(ValueError, match="prefill_kernel_takes"):
        paged_flash_prefill(q_pre, pk, pk, pages, apos_pre)
    # ... one they take lowers to a Mosaic call, bf16 and float32
    sd = jax.ShapeDtypeStruct
    for dt, page in ((jnp.bfloat16, 16), (jnp.float32, 8)):
        assert decode_kernel_takes(dt, 128, page)
        assert prefill_kernel_takes(dt, 128, page, 32)
        for fn, S in ((paged_attention_decode, 1),
                      (paged_flash_prefill, 32)):
            text = jax.jit(
                lambda qg, pool, pg, ap: fn(qg, pool, pool, pg, ap)).trace(
                sd((4, S, 4, 4, 128), dt), sd((33, page, 4, 128), dt),
                sd((4, 8), jnp.int32), sd((4, S), jnp.int32)).lower(
                lowering_platforms=("tpu",)).as_text()
            assert "tpu_custom_call" in text
    assert not decode_kernel_takes(jnp.bfloat16, 128, 8)
    assert not decode_kernel_takes(jnp.bfloat16, 64, 16)
    assert not decode_kernel_takes(jnp.int8, 128, 32)


def _cell_widths_cfg():
    """SmolLM3's widths at two layers, bf16: what the serving cells'
    programs are lowered at."""
    return T.TransformerConfig(
        vocab_size=128256, hidden_size=2048, intermediate_size=11008,
        num_hidden_layers=2, num_attention_heads=16,
        num_key_value_heads=4, rope_theta=5e6, nope_interval=4,
        tie_word_embeddings=True, dtype=jnp.bfloat16, remat=False)


@pytest.mark.parametrize("batch,pages_per,chunk", [(32, 128, 256),
                                                   (8, 512, 512)],
                         ids=["chat-256x2048", "documents-512x8192"])
def test_engine_prefill_program_lowers_for_tpu_without_the_gather(
        monkeypatch, batch, pages_per, chunk):
    """The engine's prefill-chunk program at the serving cells' shapes,
    lowered FOR a TPU on this host: attention is ONE Mosaic call that the
    layers share, nothing has the (1, V, n_kv, hd) extent of the gathered view and nothing the
    (1, n_kv, rep, S, V) float32 extent of its scores, both of which the
    gather path's program holds."""
    from distributed_training_sandbox_tpu.serving import (
        make_serve_prefill_step)
    from distributed_training_sandbox_tpu.serving.kv_pool import (
        PoolBuffers)

    cfg = _cell_widths_cfg()
    page, nkv, hd = 16, 4, 128
    rep = cfg.num_attention_heads // nkv
    V = pages_per * page
    sd = jax.ShapeDtypeStruct
    params = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0),
                                                  cfg))
    pool = tuple(sd((batch * pages_per + 1, page, nkv, hd), jnp.bfloat16)
                 for _ in range(cfg.num_hidden_layers))
    args = (PoolBuffers(k=pool, v=pool, k_scale=None, v_scale=None),
            params, sd((1, pages_per), jnp.int32),
            sd((1, chunk), jnp.int32), sd((), jnp.int32),
            sd((), jnp.int32))
    view = (f"1x{pages_per}x{page}x{nkv}x{hd}x", f"1x{V}x{nkv}x{hd}x",
            f"1x{nkv}x{V}x{hd}x")
    scores = f"1x{nkv}x{rep}x{chunk}x{V}xf32"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def lowered(paged_kernel):
        step = make_serve_prefill_step(cfg, paged_kernel=paged_kernel)
        return step.trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()

    text = lowered(True)
    # the layers share one trace of the kernel's call, so one lowering
    assert text.count("tpu_custom_call") == 1
    assert text.count("call @_prefill_float") == cfg.num_hidden_layers
    assert not any(v in text for v in view) and scores not in text
    gather = lowered(False)
    assert any(v in gather for v in view) and scores in gather


@pytest.mark.parametrize("batch,pages_per", [(32, 128), (8, 512)],
                         ids=["chat-32x2048", "documents-8x8192"])
def test_engine_decode_program_lowers_for_tpu_without_the_gather(
        monkeypatch, batch, pages_per):
    """The engine's decode program at the serving cells' shapes (SmolLM3
    widths, two layers, bf16, page 16), lowered FOR a TPU on this host:
    attention is a Mosaic call and nothing has the (B, V, n_kv, hd)
    extent of the gathered view, which the gather path's program does."""
    from distributed_training_sandbox_tpu.serving import (
        make_serve_decode_step)
    from distributed_training_sandbox_tpu.serving.kv_pool import (
        PoolBuffers)

    cfg = _cell_widths_cfg()
    page, nkv, hd = 16, 4, 128
    sd = jax.ShapeDtypeStruct
    params = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0),
                                                  cfg))
    pool = tuple(sd((batch * pages_per + 1, page, nkv, hd), jnp.bfloat16)
                 for _ in range(cfg.num_hidden_layers))
    args = (PoolBuffers(k=pool, v=pool, k_scale=None, v_scale=None),
            params, sd((batch, pages_per), jnp.int32),
            sd((batch,), jnp.int32), sd((batch,), jnp.int32),
            sd((batch,), jnp.int32), sd((batch,), jnp.bool_))
    view = (f"{batch}x{pages_per}x{page}x{nkv}x{hd}x",
            f"{batch}x{pages_per * page}x{nkv}x{hd}x",
            f"{batch}x{nkv}x{pages_per * page}x{hd}x")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def lowered(paged_kernel):
        step = make_serve_decode_step(cfg, paged_kernel=paged_kernel)
        return step.trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()

    text = lowered(True)
    assert "tpu_custom_call" in text
    assert not any(v in text for v in view)
    assert any(v in lowered(False) for v in view)


def test_matmul_kernels_lower_for_tpu(mesh8):
    """The matmul kernels compile on a v5e at the flagship's widths (chip
    smoke, PR 21).  Lowering them FOR a TPU needs no chip and is where
    Pallas rejects an API, block-shape or accumulator mistake — the
    refusals the first chip run met (``Expected matmul acc to be
    32-bit``, unaligned blocks) would fail here."""
    S, h, f = 8192, 2048, 11008
    sd = jax.ShapeDtypeStruct
    bf, i8, f8, f32 = jnp.bfloat16, jnp.int8, jnp.float8_e4m3fn, jnp.float32
    ring = C.smap(lambda a, ws: C.all_gather_matmul_pallas(
        a, ws, "dp", interpret=False), mesh8, (P(), P("dp")), P())
    for m, k, n in ((S, h, f), (S, f, h)):
        cases = (
            (lambda *a: Q.int8_matmul_pallas(*a, interpret=False),
             (sd((m, k), i8), sd((m, 1), f32), sd((k, n), i8),
              sd((1, n), f32))),
            (lambda *a: Q.int8_matmul_pallas_fused(*a, interpret=False),
             (sd((m, k), bf), sd((k, n), i8), sd((1, n), f32))),
            (lambda *a: Q.fp8_matmul_pallas(*a, interpret=False),
             (sd((m, k), f8), sd((), f32), sd((k, n), f8), sd((), f32))),
            (ring, (sd((m, k), bf), sd((k, n), bf))))
        for fn, args in cases:
            text = jax.jit(fn).trace(*args).lower(
                lowering_platforms=("tpu",)).as_text()
            assert "tpu_custom_call" in text


# ------------------------------------------- knob/planner satellites

def test_bench_name_round_trips_through_parser():
    from distributed_training_sandbox_tpu.memory_plan.planner import (
        parse_bench_config_name)
    from distributed_training_sandbox_tpu.tuner.knobs import (
        TunerCandidate)

    for prec in ("bf16", "int8_bwd", "fp8", "fp8_delayed", "fp8_pallas"):
        for remat in ("full", "save_dots"):
            for state in ("full", "int8"):
                for bs in (1, 4):
                    cand = TunerCandidate(
                        matmul_precision=prec, remat_policy=remat,
                        state_precision=state, batch_scale=bs)
                    knobs = parse_bench_config_name(cand.bench_name())
                    assert knobs is not None, cand.bench_name()
                    assert knobs["matmul_precision"] == prec
                    assert knobs["remat_policy"] == remat
                    assert knobs["state_precision"] == state
                    assert knobs["batch_scale"] == bs
    # names the grammar has no token for must parse to None, not wrong
    assert parse_bench_config_name("explicit_ring_fused_pallas") is None


def test_planner_enumerates_fp8_leg():
    from distributed_training_sandbox_tpu.memory_plan.planner import (
        QUANT_CHOICES, _QUANT_SPEED)

    assert "fp8" in QUANT_CHOICES
    # un-benched placeholder legs must not outrank the measured int8_bwd
    # anchor (measured beats multiplier optimism), but still beat bf16
    assert _QUANT_SPEED["bf16"] < _QUANT_SPEED["fp8"] \
        < _QUANT_SPEED["int8_bwd"]
    assert set(_QUANT_SPEED) >= {"fp8_delayed", "fp8_pallas"}


def test_predictor_fp8_waterline_sits_in_int8_band():
    """fp8 keeps 1-byte operand codes for the bwd dots exactly as the
    int8 recipe: same working-set multipliers, so the analytic waterline
    lands in the int8 band — above bf16, equal to int8_bwd."""
    from distributed_training_sandbox_tpu.memory_plan.predictor import (
        analytic_waterline)

    def wl(prec, policy="save_dots"):
        cfg = dataclasses.replace(T.TINY_LM, matmul_precision=prec,
                                  remat_policy=policy)
        return analytic_waterline(cfg, batch=8, seq=256, ws=8).gb

    for policy in ("full", "save_dots"):
        assert wl("fp8", policy) > wl("bf16", policy)
        assert wl("fp8", policy) == wl("int8_bwd", policy)
        assert wl("fp8_delayed", policy) == wl("fp8", policy)


# ------------------------------------------- pitfalls lint satellite

def test_pallas_interpret_lint_red_green():
    from distributed_training_sandbox_tpu.analysis.pitfalls import (
        lint_source)

    red = """
from jax.experimental import pallas as pl

def k(x):
    return pl.pallas_call(kern, out_shape=x)(x)
"""
    found = [f for f in lint_source(red)
             if f.check == "pallas-call-no-interpret"]
    assert len(found) == 1 and found[0].severity == "error"

    green = """
from jax.experimental import pallas as pl

def k(x, interpret=False):
    return pl.pallas_call(kern, out_shape=x, interpret=interpret)(x)

def fwd(x, **kw):
    return pl.pallas_call(kern, out_shape=x, **kw)(x)
"""
    assert not [f for f in lint_source(green)
                if f.check == "pallas-call-no-interpret"]

    pragma = """
from jax.experimental import pallas as pl

def k(x):
    # pallas-ok
    return pl.pallas_call(kern, out_shape=x)(x)
"""
    assert not [f for f in lint_source(pragma)
                if f.check == "pallas-call-no-interpret"]


# ------------------------------------------- ledger fp8/int8 payload

def test_hlo_sizes_fp8_dtypes_at_one_byte():
    """``_DTYPE_BYTES`` prices f8 wire traffic at 1 byte/elem — a
    synthetic f8 all-gather reports 4x fewer payload bytes than its f32
    twin of identical shape."""
    from distributed_training_sandbox_tpu.ops.hlo import (
        collective_instances)

    tmpl = ('  %%ag = %s[8,64]{1,0} all-gather(%s[1,64]{1,0} %%p), '
            'replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}\n')
    for dt in ("f8e4m3fn", "f8e5m2"):
        (f8,) = collective_instances(tmpl % (dt, dt))
        (f32,) = collective_instances(tmpl % ("f32", "f32"))
        assert f8.bytes * 4 == f32.bytes == 8 * 64 * 4, (dt, f8.bytes)


def test_ledger_reports_quantized_all_reduce_wire_bytes(mesh8):
    """Satellite acceptance: the ledger aggregates of the EQuARX
    all-reduce report the int8 wire bytes (~4x smaller than the f32
    two-shot moving the same logical tensor), not the full-precision
    logical size."""
    from distributed_training_sandbox_tpu.ops.hlo import (
        collective_instances)
    from distributed_training_sandbox_tpu.telemetry.ledger import (
        build_ledger)

    x = jax.random.normal(jax.random.PRNGKey(7), (64, 256), jnp.float32)

    def two_shot_f32(xs):
        g = C.all_gather(xs, "dp", axis=0, tiled=False)
        return jnp.sum(g, axis=0)

    def compile_text(fn):
        return jax.jit(C.smap(fn, mesh8, P("dp"), P())) \
            .lower(x).compile().as_text()

    def ledger_bytes(text):
        insts = [i for i in collective_instances(text) if i.name]
        stats = {i.name: {"count": 8, "total_us": 80.0} for i in insts}
        led = build_ledger(stats, text, axis_sizes={"dp": 8})
        assert led.unmeasured_instances == []
        aggs = led.aggregates()
        return (sum(a["bytes_moved"] for a in aggs.values()),
                [e.dtype for e in led.entries])

    q_bytes, q_dtypes = ledger_bytes(compile_text(
        lambda xs: Q.quantized_all_reduce(xs, "dp")))
    f_bytes, f_dtypes = ledger_bytes(compile_text(two_shot_f32))
    # the codes travel as s8 — the dominant wire dtype
    assert "s8" in q_dtypes and set(f_dtypes) == {"f32"}
    ratio = f_bytes / q_bytes
    # scales gather adds a small f32 side channel: ~4x, not exactly 4
    assert 3.0 < ratio <= 4.0, (q_bytes, f_bytes, ratio)


# ----------------------- measured ledger verdicts for the new contracts

NEW_CONTRACTS = ("fsdp_fp8", "fsdp_ring_fused_pallas", "tp_q8",
                 "serve_decode_paged_kernel")


@pytest.mark.parametrize("strategy", NEW_CONTRACTS)
def test_new_contracts_get_measured_ledger_verdict(strategy, tmp_path):
    """Profiled smoke run of each new choreography on the CPU mesh:
    static contract verdict ok, and the trace⋈HLO ledger join measures
    every contract-expected site with zero unmatched events."""
    from distributed_training_sandbox_tpu.analysis import check_counts
    from distributed_training_sandbox_tpu.analysis.fixtures import (
        build_strategy)
    from distributed_training_sandbox_tpu.ops.hlo import (
        count_collectives)
    from distributed_training_sandbox_tpu.telemetry.ledger import (
        build_ledger, join_contract)
    from distributed_training_sandbox_tpu.utils.trace_analysis import (
        collective_event_stats, latest_xplane_file)

    b = build_strategy(strategy)
    lowered = b.step.lower(*b.args)
    verdict = check_counts(b.contract,
                           count_collectives(lowered.as_text()), b.ctx)
    assert verdict.ok, verdict.summary()
    hlo = lowered.compile().as_text()

    args = b.args
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(2):
            out = b.step(*args)
            args = b.advance(args, out)
        jax.block_until_ready(out)

    tf = latest_xplane_file(str(tmp_path))
    assert tf is not None, "profiler wrote no trace"
    led = build_ledger(collective_event_stats(tf), hlo,
                       dict(b.mesh.shape))
    join = join_contract(led, verdict.expected, strategy)
    assert join["ok"], join["violations"]
    assert led.unmatched_events == {}
    assert led.unmeasured_instances == []
    assert led.entries, "no collective was measured"
