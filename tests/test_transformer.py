"""Transformer LM: init/loss sanity, remat equivalence, causality, NoPE
schedule, and the packed-data contract (reference ``fsdp/utils.py:29-91``)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_sandbox_tpu.data import (
    pack_tokens, synthetic_token_stream, make_packed_dataset)
from distributed_training_sandbox_tpu.models import transformer as T


CFG = T.TINY_LM


@pytest.fixture(scope="module")
def setup():
    params = T.init_params(jax.random.PRNGKey(0), CFG)
    ii, ll = make_packed_dataset(32, CFG.vocab_size, source="synthetic",
                                 num_tokens=12 * 33)
    batch = (jnp.asarray(ii[:4]), jnp.asarray(ll[:4]))
    return params, batch


def test_param_count_matches_tree(setup):
    params, _ = setup
    actual = sum(l.size for l in jax.tree.leaves(params))
    assert actual == CFG.param_count()


def test_smollm3_3b_scale():
    # the reference benchmarks "SmolLM3-3B" (fsdp/train_fsdp.py:61-64)
    assert 3.0e9 < T.SMOLLM3_3B.param_count() < 3.2e9


def test_init_loss_near_uniform(setup):
    params, batch = setup
    loss = float(T.lm_loss(params, batch, CFG))
    # random init ≈ uniform predictive distribution -> loss ≈ ln(vocab)
    assert abs(loss - np.log(CFG.vocab_size)) < 0.3


def test_remat_matches_no_remat(setup):
    params, batch = setup
    base = jax.jit(lambda p, b: T.lm_loss(p, b, CFG))(params, batch)
    cfg_r = dataclasses.replace(CFG, remat=True)
    remat = jax.jit(lambda p, b: T.lm_loss(p, b, cfg_r))(params, batch)
    assert float(base) == pytest.approx(float(remat), abs=1e-5)
    g1 = jax.jit(jax.grad(lambda p, b: T.lm_loss(p, b, CFG)))(params, batch)
    g2 = jax.jit(jax.grad(lambda p, b: T.lm_loss(p, b, cfg_r)))(params, batch)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-5)


def test_causality(setup):
    """Perturbing a future token must not change earlier logits."""
    params, batch = setup
    ids = batch[0][:1]
    logits = T.forward(params, ids, CFG)
    ids2 = ids.at[0, -1].set((ids[0, -1] + 7) % CFG.vocab_size)
    logits2 = T.forward(params, ids2, CFG)
    np.testing.assert_allclose(np.asarray(logits[0, :-1], np.float32),
                               np.asarray(logits2[0, :-1], np.float32),
                               atol=1e-5)
    # ...and the last position MUST change (the perturbed token feeds it)
    assert not np.allclose(np.asarray(logits[0, -1], np.float32),
                           np.asarray(logits2[0, -1], np.float32))


def test_flash_attention_does_not_give_way_to_xla():
    """A length splash cannot tile is an error, not a quiet run of the
    einsum path under the kernel's name."""
    q = jnp.zeros((1, 100, 4, 16))
    with pytest.raises(ValueError, match="multiple of 128"):
        T._attention_flash(q, q, q, 0.25)


def test_nope_schedule():
    flags = np.asarray(T._rope_flags(T.SMOLLM3_3B))
    # every 4th layer (3, 7, 11, ...) skips RoPE — SmolLM3's NoPE scheme
    assert not flags[3] and not flags[7] and not flags[35]
    assert flags[0] and flags[1] and flags[2] and flags[4]
    assert np.asarray(T._rope_flags(
        dataclasses.replace(CFG, nope_interval=0))).all()


def test_gqa_changes_nothing_structural(setup):
    """MHA (kv=heads) and GQA configs both run and give finite loss."""
    cfg = dataclasses.replace(CFG, num_key_value_heads=CFG.num_attention_heads)
    params = T.init_params(jax.random.PRNGKey(1), cfg)
    _, batch = setup
    assert np.isfinite(float(T.lm_loss(params, batch, cfg)))


def test_tied_vs_untied_head(setup):
    cfg = dataclasses.replace(CFG, tie_word_embeddings=False)
    params = T.init_params(jax.random.PRNGKey(2), cfg)
    assert "lm_head" in params
    _, batch = setup
    assert np.isfinite(float(T.lm_loss(params, batch, cfg)))


# ----------------------------------------------------------------- data

def test_pack_tokens_contract():
    stream = np.arange(100, dtype=np.int32)
    ii, ll = pack_tokens(stream, 9)  # window=10 -> 10 windows
    assert ii.shape == (10, 9) and ll.shape == (10, 9)
    # labels are inputs shifted by one (fsdp/utils.py:58-89)
    np.testing.assert_array_equal(ii[0], np.arange(9))
    np.testing.assert_array_equal(ll[0], np.arange(1, 10))
    np.testing.assert_array_equal(ii[:, 1:], ll[:, :-1])


def test_pack_tokens_drops_ragged_tail():
    ii, _ = pack_tokens(np.zeros(25, np.int32), 9)
    assert ii.shape == (2, 9)
    with pytest.raises(ValueError):
        pack_tokens(np.zeros(5, np.int32), 9)


def test_synthetic_stream_deterministic_and_skewed():
    a = synthetic_token_stream(10_000, 256, seed=7)
    b = synthetic_token_stream(10_000, 256, seed=7)
    np.testing.assert_array_equal(a, b)
    assert (a >= 0).all() and (a < 256).all()
    counts = np.bincount(a, minlength=256)
    # Zipf: most-frequent token much more common than the tail
    assert counts[np.argsort(counts)[-1]] > 5 * counts[counts > 0].mean()


def test_streamed_loss_matches_dense(setup):
    """Streamed cross-entropy == dense fp32 log-softmax through the whole
    model, for budgets that give several row blocks and one."""
    params, batch = setup
    dense = jax.jit(jax.value_and_grad(lambda p, b: T.lm_loss(p, b, CFG)))
    l0, g0 = dense(params, batch)
    for chunk in (100, 512):
        cfg_c = dataclasses.replace(CFG, loss_vocab_chunk=chunk)
        l1, g1 = jax.jit(jax.value_and_grad(
            lambda p, b: T.lm_loss(p, b, cfg_c)))(params, batch)
        assert float(l1) == pytest.approx(float(l0), abs=1e-5)
        for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32), atol=1e-6)


#: (B, S, H, V, chunk, dtype) -> the rows a block the whole batch derives
HEAD_CASES = {
    "odd_vocab": (2, 8, 16, 37, 10, jnp.float32),        # 4 rows x 4
    "batch_1": (1, 16, 16, 37, 37, jnp.float32),         # one block
    "ragged_rows": (3, 14, 16, 512, 128, jnp.float32),   # 9 x 5, 3 padded
    "tile_rows": (1, 300, 32, 512, 300, jnp.float32),    # 128 x 3, 84 padded
    "bf16": (2, 96, 32, 512, 300, jnp.bfloat16),         # 96 x 2
}


def _dense_head(x, w, labels):
    """3 * (dense float32 cross-entropy) + 1, its dx and dW."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    return jax.value_and_grad(
        lambda x, w: 3 * T.xent_from_hidden(x, w, labels) + 1,
        (0, 1))(f32(x), f32(w))


@pytest.mark.parametrize("wrap", ["jit", "shard_map"])
@pytest.mark.parametrize("head", ["tied", "untied"])
@pytest.mark.parametrize("case", HEAD_CASES)
def test_streamed_softmax_xent_matches_dense(case, head, wrap):
    """The row-block head against the dense float32 path: loss, dx and dW
    under a cotangent that is not 1 (``3 * loss + 1``); ``untied`` takes
    the gradient through the transpose of an (H, V) leaf, as the
    pipeline's last stage does; ``shard_map`` runs it on two devices'
    halves of the sequence, each deriving its own block from local shapes
    (the halves of ``ragged_rows`` pad too: 21 tokens in blocks of 5)."""
    from distributed_training_sandbox_tpu.ops.collectives import smap
    from distributed_training_sandbox_tpu.utils import make_mesh
    from jax.sharding import PartitionSpec as P
    B, S, H, V, chunk, dtype = HEAD_CASES[case]
    x = jax.random.normal(jax.random.PRNGKey(3), (B, S, H)).astype(dtype)
    w = jax.random.normal(jax.random.PRNGKey(4), (V, H)).astype(dtype)
    labels = jax.random.randint(jax.random.PRNGKey(5), (B, S), 0, V)
    want, (dx0, dw0) = _dense_head(x, w, labels)

    leaf = w if head == "tied" else w.T
    rows = (lambda a: a) if head == "tied" else (lambda a: a.T)

    def streamed(x, leaf, labels):
        return jax.value_and_grad(
            lambda x, leaf: 3 * T.streamed_softmax_xent(
                x, rows(leaf), labels, chunk) + 1, (0, 1))(x, leaf)

    if wrap == "shard_map":
        mesh = make_mesh({"dp": 2}, devices=jax.devices()[:2],
                         register=False)

        def local(x, leaf, labels):
            loss, (dx, dw) = streamed(x, leaf, labels)
            return jax.lax.pmean(loss, "dp"), (
                dx / 2, jax.lax.pmean(dw.astype(jnp.float32), "dp"))

        seq = P(None, "dp")
        fn = smap(local, mesh, in_specs=(seq, P(), seq),
                  out_specs=(P(), (seq, P())))
    else:
        fn = streamed
    got, (dx1, dw1) = jax.jit(fn)(x, leaf, labels)

    assert dx1.dtype == dtype
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    assert float(got) == pytest.approx(float(want), rel=tol)
    for a, b in ((dx0, dx1), (dw0, rows(dw1))):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(b, a, atol=tol * np.abs(a).max())


def _eqns(jaxpr):
    """Every equation of ``jaxpr``, those of nested jaxprs included."""
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for x in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(x, "jaxpr", x)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


@pytest.mark.parametrize("differentiated, products", [(True, 3), (False, 1)])
def test_streamed_head_multiplies_by_the_vocabulary(setup, differentiated,
                                                    products):
    """The mechanism is in the step: the gradient of ``lm_loss`` holds
    exactly three products with the vocabulary as a dimension (logits, dx,
    dW), the loss alone one (its primal makes no gradient), all inside the
    block's scan; and no array of either outgrows one block of rows by the
    vocabulary (or the (V, H) embedding and its gradient)."""
    params, batch = setup
    cfg = dataclasses.replace(CFG, loss_vocab_chunk=128)
    V, H = cfg.vocab_size, cfg.hidden_size
    tokens = batch[1].size
    block = T._loss_row_block(tokens, V, cfg.loss_vocab_chunk)
    assert 1 < block < tokens
    fn = lambda p: T.lm_loss(p, batch, cfg)  # noqa: E731
    jaxpr = jax.make_jaxpr(jax.grad(fn) if differentiated else fn)(params)

    def shapes(e):
        return [v.aval.shape for v in (*e.invars, *e.outvars)
                if hasattr(v.aval, "shape")]

    dots = [e for e in _eqns(jaxpr.jaxpr) if e.primitive.name == "dot_general"
            and any(V in s for s in shapes(e))]
    assert len(dots) == products
    top_level = [e for e in jaxpr.jaxpr.eqns
                 if e.primitive.name == "dot_general"]
    assert not any(V in s for e in top_level for s in shapes(e))
    for e in _eqns(jaxpr.jaxpr):
        for s in shapes(e):
            if V in s:
                assert np.prod(s) // V <= max(block, H), (e.primitive, s)


@pytest.mark.parametrize("policy", ["save_attn", "save_dots"])
def test_remat_policy_matches(setup, policy):
    params, batch = setup
    cfg_s = dataclasses.replace(CFG, remat=True, remat_policy=policy)
    base = float(jax.jit(lambda p, b: T.lm_loss(p, b, CFG))(params, batch))
    saved = float(jax.jit(lambda p, b: T.lm_loss(p, b, cfg_s))(params, batch))
    assert saved == pytest.approx(base, abs=1e-5)
    g = jax.jit(jax.grad(lambda p, b: T.lm_loss(p, b, cfg_s)))(params, batch)
    assert all(np.isfinite(np.asarray(x, np.float32)).all()
               for x in jax.tree.leaves(g))
