"""Overlap engine: ring-decomposed collectives and their train-step
variants.  The headline invariants:

  * ``ring_all_gather`` / ``decomposed_all_reduce`` are BITWISE equal to
    their monolithic twins (values AND grads) — the decomposition moves
    data and pins the reduction arithmetic + backward to the monolithic
    ops, so ``--overlap ring`` fsdp/tp loss sequences are
    bitwise-identical to ``--overlap none`` on the 8-way CPU mesh;
  * the fused collective matmuls (``all_gather_matmul`` /
    ``matmul_reduce_scatter``) agree with gather-then-matmul up to fp
    re-association (exact on integer-valued inputs), and their ring
    error paths speak (degenerate axis, non-divisible dims);
  * microbatched gradient accumulation (``--accum-steps k``) tracks one
    full-batch step within fp re-association of the batch reduction;
  * the ring variants' choreography (ppermute hop counts, zero
    all_gather sites) matches the registered contracts.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from distributed_training_sandbox_tpu.data import make_packed_dataset
from distributed_training_sandbox_tpu.models import transformer as T
from distributed_training_sandbox_tpu.ops import collectives as C
from distributed_training_sandbox_tpu.ops import count_collectives
from distributed_training_sandbox_tpu.parallel import fsdp, tensor

CFG = T.TINY_LM


@pytest.fixture(scope="module")
def mesh4x2():
    return Mesh(np.array(jax.devices()).reshape(4, 2), ("dp", "tp"))


@pytest.fixture(scope="module")
def mesh8x1():
    """Second axis of size 1 — the degenerate ring."""
    return Mesh(np.array(jax.devices()).reshape(8, 1), ("dp", "one"))


@pytest.fixture(scope="module")
def lm_setup(mesh8):
    params = T.init_params(jax.random.PRNGKey(0), CFG)
    ii, ll = make_packed_dataset(32, CFG.vocab_size, source="synthetic",
                                 num_tokens=40 * 33)
    batch = (jnp.asarray(ii[:8]), jnp.asarray(ll[:8]))
    batch16 = (jnp.asarray(ii[:16]), jnp.asarray(ll[:16]))
    shards = fsdp.shard_params_fsdp(params, mesh8)
    opt = fsdp.init_fsdp_opt_state(shards)
    return params, shards, opt, batch, batch16


# ------------------------------------------------------- ring primitives

def test_ring_all_gather_bitwise(mesh8):
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 32)) * 2.3
    ref = jax.jit(C.smap(lambda v: C.all_gather(v, "dp", axis=0),
                         mesh8, P("dp"), P()))(x)
    out = jax.jit(C.smap(lambda v: C.ring_all_gather(v, "dp", 0),
                         mesh8, P("dp"), P()))(x)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))
    # grads too: the custom_vjp backward IS the monolithic psum_scatter
    g_ref = jax.jit(C.smap(
        jax.grad(lambda v: jnp.sum(C.all_gather(v, "dp", axis=0) ** 2)),
        mesh8, P("dp"), P("dp")))(x)
    g_out = jax.jit(C.smap(
        jax.grad(lambda v: jnp.sum(C.ring_all_gather(v, "dp", 0) ** 2)),
        mesh8, P("dp"), P("dp")))(x)
    np.testing.assert_array_equal(np.asarray(g_ref), np.asarray(g_out))


def test_ring_all_gather_hop_count(mesh8):
    x = jnp.ones((64, 4))
    f = jax.jit(C.smap(lambda v: C.ring_all_gather(v, "dp", 0),
                       mesh8, P("dp"), P()))
    c = count_collectives(f, x)
    assert c["collective_permute"] == 7          # ws-1 hops
    assert c["all_gather"] == 0                  # nothing monolithic


def test_decomposed_all_reduce_bitwise(mesh8):
    """THE load-bearing fact: psum_scatter + ring gather == psum
    bitwise (reduction order shared, reassembly exact), and the pinned
    backward is psum's own transpose."""
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 64)) * 3.1
    ref = jax.jit(C.smap(lambda v: lax.psum(v, "dp"),
                         mesh8, P("dp"), P()))(x)
    out = jax.jit(C.smap(lambda v: C.decomposed_all_reduce(v, "dp", -1),
                         mesh8, P("dp"), P()))(x)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))
    g_ref = jax.jit(C.smap(
        jax.grad(lambda v: jnp.sum(lax.psum(v, "dp") ** 2)),
        mesh8, P("dp"), P("dp")))(x)
    g_out = jax.jit(C.smap(
        jax.grad(lambda v: jnp.sum(
            C.decomposed_all_reduce(v, "dp", -1) ** 2)),
        mesh8, P("dp"), P("dp")))(x)
    np.testing.assert_array_equal(np.asarray(g_ref), np.asarray(g_out))


def test_all_gather_matmul_matches_gather_then_matmul(mesh8):
    a = jax.random.normal(jax.random.PRNGKey(2), (16, 64))
    w = jax.random.normal(jax.random.PRNGKey(3), (64, 24))
    ref = jax.jit(C.smap(
        lambda aa, ws: aa @ C.all_gather(ws, "dp", axis=0),
        mesh8, (P(), P("dp")), P()))(a, w)
    out = jax.jit(C.smap(lambda aa, ws: C.all_gather_matmul(aa, ws, "dp"),
                         mesh8, (P(), P("dp")), P()))(a, w)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=1e-5, atol=1e-5)
    # AD transpose (the implicit ring matmul-reduce-scatter) agrees with
    # the gather path's psum_scatter backward
    g_ref = jax.jit(C.smap(
        jax.grad(lambda ws: jnp.sum(
            (a @ C.all_gather(ws, "dp", axis=0)) ** 2)),
        mesh8, P("dp"), P("dp")))(w)
    g_out = jax.jit(C.smap(
        jax.grad(lambda ws: jnp.sum(C.all_gather_matmul(a, ws, "dp") ** 2)),
        mesh8, P("dp"), P("dp")))(w)
    # the chunked contraction reassociates each K-sum, and an element's
    # error follows the size of the terms summed, not of the (possibly
    # cancelled) result — so the absolute tolerance scales with the
    # tensor: 1e-6 of its largest entry is ~8 float32 ulps there
    g_ref = np.asarray(g_ref)
    np.testing.assert_allclose(g_ref, np.asarray(g_out), rtol=1e-4,
                               atol=1e-6 * np.abs(g_ref).max())


def test_matmul_reduce_scatter_matches_monolithic(mesh8):
    a = jax.random.normal(jax.random.PRNGKey(4), (64, 32))
    b = jax.random.normal(jax.random.PRNGKey(5), (32, 16))
    ref = jax.jit(C.smap(
        lambda u, v: lax.psum_scatter(u @ v, "dp", scatter_dimension=0,
                                      tiled=True),
        mesh8, (P(), P()), P("dp")))(a, b)
    out = jax.jit(C.smap(lambda u, v: C.matmul_reduce_scatter(u, v, "dp"),
                         mesh8, (P(), P()), P("dp")))(a, b)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=1e-4, atol=1e-4)
    # integer-valued floats add exactly -> the ring order is immaterial
    ai, bi = jnp.round(a * 3), jnp.round(b * 3)
    ref = jax.jit(C.smap(
        lambda u, v: lax.psum_scatter(u @ v, "dp", scatter_dimension=0,
                                      tiled=True),
        mesh8, (P(), P()), P("dp")))(ai, bi)
    out = jax.jit(C.smap(lambda u, v: C.matmul_reduce_scatter(u, v, "dp"),
                         mesh8, (P(), P()), P("dp")))(ai, bi)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))


def test_ring_degenerate_axis_falls_back(mesh8x1):
    """Axis of size 1: every ring helper degrades to the plain local op
    instead of building a 0-hop ring."""
    a = jax.random.normal(jax.random.PRNGKey(6), (16, 64))
    w = jax.random.normal(jax.random.PRNGKey(7), (64, 8))
    out = jax.jit(C.smap(
        lambda aa, ws: C.all_gather_matmul(aa, ws, "one"),
        mesh8x1, (P(), P()), P()))(a, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(a @ w),
                               rtol=1e-5, atol=1e-5)
    g = jax.jit(C.smap(lambda v: C.ring_all_gather(v, "one", 0),
                       mesh8x1, P(), P()))(a)
    np.testing.assert_array_equal(np.asarray(g), np.asarray(a))
    r = jax.jit(C.smap(lambda v: C.decomposed_all_reduce(v, "one", -1),
                       mesh8x1, P(), P()))(a)
    np.testing.assert_array_equal(np.asarray(r), np.asarray(a))
    m = jax.jit(C.smap(lambda u: C.matmul_reduce_scatter(u, w, "one"),
                       mesh8x1, P(), P()))(a)
    np.testing.assert_allclose(np.asarray(m), np.asarray(a @ w),
                               rtol=1e-5, atol=1e-5)


def test_ring_divisibility_errors_speak(mesh8):
    """Satellite: explicit error messages instead of opaque reshape /
    dynamic-slice failures."""
    a = jnp.ones((16, 56))          # 56 != 8 * 8
    w = jnp.ones((8, 8))

    def agm(aa):
        return C.all_gather_matmul(aa, w, "dp")

    with pytest.raises(ValueError, match="contraction dim 56"):
        jax.jit(C.smap(agm, mesh8, P(), P()))(a)

    def mrs(u):
        return C.matmul_reduce_scatter(u, jnp.ones((56, 8)), "dp")

    with pytest.raises(ValueError, match="not divisible"):
        jax.jit(C.smap(mrs, mesh8, P(), P("dp")))(jnp.ones((28, 56)))

    def dar(v):
        return C.decomposed_all_reduce(v, "dp", -1)

    with pytest.raises(ValueError, match="not divisible"):
        jax.jit(C.smap(dar, mesh8, P(), P()))(jnp.ones((4, 7)))


# ------------------------------------------------- fsdp ring train steps

def _run_steps(step, shards, opt, batch, n=4):
    losses = []
    for _ in range(n):
        shards, opt, loss = step(shards, opt, batch)
        losses.append(np.asarray(loss).item())
    return losses, shards


def test_fsdp_ring_bitwise_loss_parity(lm_setup, mesh8):
    """Acceptance: --overlap ring loss sequences bitwise-identical to
    --overlap none, params included."""
    _, shards, opt, batch, _ = lm_setup
    s_none = fsdp.make_fsdp_train_step(shards, CFG, mesh8, donate=False)
    s_ring = fsdp.make_fsdp_train_step(shards, CFG, mesh8, donate=False,
                                       overlap="ring")
    l0, p0 = _run_steps(s_none, shards, opt, batch)
    l1, p1 = _run_steps(s_ring, shards, opt, batch)
    assert l0 == l1
    for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(p1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fsdp_ring_choreography(lm_setup, mesh8):
    """No monolithic gather sites survive: 11 leaves x 7 hops, one
    psum_scatter per leaf in the backward — and the registered
    fsdp_ring contract agrees."""
    from distributed_training_sandbox_tpu.analysis import evaluate_contract

    _, shards, opt, batch, _ = lm_setup
    s_ring = fsdp.make_fsdp_train_step(shards, CFG, mesh8, donate=False,
                                       overlap="ring")
    c = count_collectives(s_ring, shards, opt, batch)
    n_leaves = len(jax.tree.leaves(shards))
    assert c["all_gather"] == 0
    assert c["collective_permute"] == n_leaves * 7
    assert c["reduce_scatter"] == n_leaves
    verdict = evaluate_contract("fsdp_ring", c, params=shards, mesh=mesh8,
                                n_layers=CFG.num_hidden_layers)
    assert verdict.ok, verdict.summary()


def test_fsdp_ring_fused_collective_matmul(lm_setup, mesh8):
    """ring_fused: projection weights never gather — their matmuls run
    as all_gather_matmul (zero all_gather sites, ppermute rings in fwd
    AND the AD-transposed bwd) and the loss tracks the baseline to fp
    re-association."""
    _, shards, opt, batch, _ = lm_setup
    s_none = fsdp.make_fsdp_train_step(shards, CFG, mesh8, donate=False)
    s_fuse = fsdp.make_fsdp_train_step(shards, CFG, mesh8, donate=False,
                                       overlap="ring_fused")
    l0, p0 = _run_steps(s_none, shards, opt, batch, n=3)
    l1, p1 = _run_steps(s_fuse, shards, opt, batch, n=3)
    np.testing.assert_allclose(l0, l1, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(p1)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-4)
    c = count_collectives(s_fuse, shards, opt, batch)
    assert c["all_gather"] == 0
    assert c["collective_permute"] > 7 * 7      # fused fwd+bwd rings
    # only the non-matmul leaves (ln1, ln2, embed, final_norm) keep a
    # psum_scatter backward
    assert c["reduce_scatter"] == 4


def test_fsdp_ring_fused_guards():
    with pytest.raises(ValueError, match="ring_fused"):
        fsdp.make_fsdp_train_step(
            {}, CFG, Mesh(np.array(jax.devices()).reshape(8), ("dp",)),
            overlap="ring_fused", reshard_after_forward=False)
    with pytest.raises(ValueError, match="overlap="):
        fsdp.make_fsdp_train_step(
            {}, CFG, Mesh(np.array(jax.devices()).reshape(8), ("dp",)),
            overlap="spiral")


# --------------------------------------------------- tp ring train steps

def test_tp_ring_bitwise_loss_parity(lm_setup, mesh4x2):
    params, _, _, batch, _ = lm_setup
    shards = tensor.shard_params_tp(params, mesh4x2)
    opt = fsdp.init_fsdp_opt_state(shards)
    t_none = tensor.make_tp_train_step(shards, CFG, mesh4x2, donate=False)
    t_ring = tensor.make_tp_train_step(shards, CFG, mesh4x2, donate=False,
                                       overlap="ring")
    l0, p0 = _run_steps(t_none, shards, opt, batch)
    l1, p1 = _run_steps(t_ring, shards, opt, batch)
    assert l0 == l1
    for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(p1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_tp_ring_choreography(lm_setup, mesh4x2):
    from distributed_training_sandbox_tpu.analysis import evaluate_contract

    params, _, _, batch, _ = lm_setup
    shards = tensor.shard_params_tp(params, mesh4x2)
    opt = fsdp.init_fsdp_opt_state(shards)
    t_ring = tensor.make_tp_train_step(shards, CFG, mesh4x2, donate=False,
                                       overlap="ring")
    c = count_collectives(t_ring, shards, opt, batch)
    assert c["reduce_scatter"] == 2              # the two rejoin RS sites
    assert c["collective_permute"] == 2          # 2 sites x (tp-1) hops
    verdict = evaluate_contract("tp_ring", c, params=shards, mesh=mesh4x2,
                                n_layers=CFG.num_hidden_layers)
    assert verdict.ok, verdict.summary()


# ----------------------------------------------- gradient accumulation

def test_accum_steps_parity(lm_setup, mesh8):
    """--accum-steps k at microbatch B/k tracks one step at batch B:
    the only deviation allowed is fp re-association of the batch
    reduction (the losses agree to ~1 ulp of f32, params to 1e-5)."""
    _, shards, opt, _, batch16 = lm_setup
    s_full = fsdp.make_fsdp_train_step(shards, CFG, mesh8, donate=False)
    s_accum = fsdp.make_fsdp_train_step(shards, CFG, mesh8, donate=False,
                                        accum_steps=2)
    l0, p0 = _run_steps(s_full, shards, opt, batch16, n=3)
    l1, p1 = _run_steps(s_accum, shards, opt, batch16, n=3)
    np.testing.assert_allclose(l0, l1, rtol=2e-6)
    for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(p1)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-5)


def test_accum_steps_composes_with_ring(lm_setup, mesh8):
    """ring stays bitwise under accumulation: accum+ring equals accum
    alone exactly (the ring replaces collectives 1:1 inside each
    microbatch)."""
    _, shards, opt, _, batch16 = lm_setup
    s_accum = fsdp.make_fsdp_train_step(shards, CFG, mesh8, donate=False,
                                        accum_steps=2)
    s_both = fsdp.make_fsdp_train_step(shards, CFG, mesh8, donate=False,
                                       accum_steps=2, overlap="ring")
    l0, p0 = _run_steps(s_accum, shards, opt, batch16, n=3)
    l1, p1 = _run_steps(s_both, shards, opt, batch16, n=3)
    assert l0 == l1
    for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(p1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_accum_steps_divisibility_error(lm_setup, mesh8):
    _, shards, opt, batch, _ = lm_setup      # local batch 1 on 8 devices
    step = fsdp.make_fsdp_train_step(shards, CFG, mesh8, donate=False,
                                     accum_steps=3)
    with pytest.raises(ValueError, match="accum_steps=3 must divide"):
        step(shards, opt, batch)


def test_tp_accum_steps(lm_setup, mesh4x2):
    params, _, _, _, batch16 = lm_setup
    shards = tensor.shard_params_tp(params, mesh4x2)
    opt = fsdp.init_fsdp_opt_state(shards)
    t_full = tensor.make_tp_train_step(shards, CFG, mesh4x2, donate=False)
    t_accum = tensor.make_tp_train_step(shards, CFG, mesh4x2,
                                        donate=False, accum_steps=2)
    l0, _ = _run_steps(t_full, shards, opt, batch16, n=2)
    l1, _ = _run_steps(t_accum, shards, opt, batch16, n=2)
    np.testing.assert_allclose(l0, l1, rtol=2e-6)
