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


def test_chunked_loss_matches_dense(setup):
    """Streamed-vocab cross-entropy == dense fp32 log-softmax, for chunk
    sizes that do and don't divide the vocab (padding + mask path)."""
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


def test_chunked_softmax_xent_direct():
    from distributed_training_sandbox_tpu.models.transformer import (
        chunked_softmax_xent)
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (2, 8, 16))
    w = jax.random.normal(jax.random.PRNGKey(4), (37, 16))  # odd vocab
    labels = jax.random.randint(jax.random.PRNGKey(5), (2, 8), 0, 37)
    logits = x @ w.T
    want = float(jnp.mean(jax.scipy.special.logsumexp(logits, -1)
                          - jnp.take_along_axis(logits, labels[..., None],
                                                -1)[..., 0]))
    got = float(chunked_softmax_xent(x, w, labels, chunk=10))
    assert got == pytest.approx(want, abs=1e-5)


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
