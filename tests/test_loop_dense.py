"""The looped dense block (``models/loop_dense.py``) on the serving path, at
the benchmark configuration's rehearsal size, float32, seeded weights, on
the CPU: the cache-less forward and the engine's own programs against the
benchmark's plain reference on logits, through the cache, across a
prefill-chunk boundary and a slot's second grant; the exit choice at three
thresholds; that every pass of every token runs against caches of its own
and the head runs once a row; the pool's 192 caches; what is refused by
name and what is not (a shared prefix)."""

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmarks.counts import loop_dense as N  # noqa: E402
from benchmarks.reference import loop_dense as R  # noqa: E402
from distributed_training_sandbox_tpu.models import loop_dense as LD  # noqa: E402
from distributed_training_sandbox_tpu.models import transformer as T  # noqa: E402
from distributed_training_sandbox_tpu.serving import ServingEngine  # noqa: E402
from distributed_training_sandbox_tpu.serving import engine as E  # noqa: E402
from distributed_training_sandbox_tpu.serving import kv_pool  # noqa: E402
from distributed_training_sandbox_tpu.serving.kv_pool import PagedKVPool  # noqa: E402
from tests.serving_blocks import FIELDS as BLOCK_FIELDS  # noqa: E402
from tests.serving_blocks import reference_tokens, serve_logits  # noqa: E402

CONFIG = json.loads(
    (ROOT / "benchmarks/configs/ouro-2.6b-serve.json").read_text())
FIELDS = BLOCK_FIELDS["loop_dense"]
L, PASSES = 3, 3


def make(seed=0, scale=2.0, **over):
    """Seeded weights, scaled as the benchmark scales them; every norm's
    weight (initialised 1) and the gate's bias (0) moved off their init, so
    that each is exercised, and the gate's weight widened, so that at 64
    wide rows leave at different passes."""
    fields = {**FIELDS, **over}
    cfg = T.TransformerConfig(**fields, dtype=jnp.float32, remat=False)
    params = jax.tree.map(lambda x: x * scale,
                          T.init_params(jax.random.key(seed), cfg))
    key = jax.random.key(seed + 100)

    def off_init(path, x):
        name = str(path[-1]).strip("[]'")
        k = jax.random.fold_in(key, sum(map(ord, str(path))))
        if "ln" in name or "norm" in name:
            return x + 0.3 * jax.random.normal(k, x.shape, x.dtype)
        if "exit_gate" in str(path):
            return 0.1 + x if name == "b" else 8.0 * x
        return x

    return fields, cfg, jax.tree_util.tree_map_with_path(off_init, params)


@pytest.fixture(scope="module")
def model():
    return make()


def test_the_block_is_selected_and_counted(model):
    fields, cfg, params = model
    assert cfg.loop_dense and cfg.block_module is LD
    assert cfg.layer_passes == PASSES and not cfg.state_slots
    assert cfg.linear_mixer is None
    assert {**FIELDS, **CONFIG["rehearse"]["fields"], "dtype": None} \
        == {**FIELDS, "dtype": None}       # the rehearsal's size
    assert LD.layer_kinds(cfg) == ("full",) * L
    lw = params["layers"][0]
    assert sorted(lw) == ["ln1", "ln2", "post_attn_norm", "post_mlp_norm",
                          "w_down", "w_gate", "w_qkv", "w_up", "wo"]
    assert lw["w_qkv"].shape == (64, 3 * 64) and lw["wo"].shape == (64, 64)
    assert params["lm_head"].shape == (64, 512)
    assert params["exit_gate"]["w"].shape == (64,)
    assert params["exit_gate"]["b"].shape == (1,)
    assert cfg.param_count() == N.param_count(fields) \
        == sum(x.size for x in jax.tree.leaves(params))
    assert LD.COUNTERS == LD.DEVICE_COUNTERS == (
        "ut_passes", "exit_step_sum", "early_exit_rows")
    # at the published fields: shapes only, nothing allocated
    pub = CONFIG["fields"]
    big = T.TransformerConfig(**{**pub, "dtype": jnp.bfloat16})
    assert big.param_count() == N.param_count(pub) == 2_667_974_657
    shapes = jax.eval_shape(lambda: T.init_params(jax.random.key(0), big))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 2_667_974_657
    assert shapes["layers"][47]["w_qkv"].shape == (2048, 6144)
    assert N.kv_bytes_per_token(pub) == 1_572_864
    assert N.decode_step_bytes(pub, 0) == 2 * (
        4 * 48 * 51_388_416 + 49_152 * 2048 + 2 * 2048 + 1)


def test_the_pool_counts_a_cache_a_pass_a_layer():
    cfg = T.TransformerConfig(**{**CONFIG["fields"], "dtype": jnp.bfloat16})
    assert kv_pool.layer_kinds(cfg) == ("full",) * 192
    assert kv_pool.paged_layers(cfg) == 192
    assert kv_pool.row_layout(cfg) == ((16, 128), True)
    assert kv_pool.token_row_bytes(cfg) == 8192
    assert kv_pool.slot_state_bytes(cfg) == 0
    eng = json.loads((ROOT / "benchmarks/workloads/"
                      "short-reasoning-backlog.json").read_text())["engine"]
    n_pages = eng["max_batch"] * eng["max_seq_len"] // eng["page_size"] + 1
    bufs = jax.eval_shape(
        lambda: PagedKVPool(cfg, n_pages, eng["page_size"]).bufs)
    assert len(bufs.k) == len(bufs.v) == 192 and bufs.conv is None
    assert {a.shape for a in bufs.k + bufs.v} == {(321, 16, 16, 128)}
    total = sum(a.size * a.dtype.itemsize for a in bufs.k + bufs.v)
    assert total == 321 * 16 * 1_572_864 == 8_078_229_504
    from distributed_training_sandbox_tpu.serving.accounting import page_bytes
    assert page_bytes(cfg, 16) == 16 * 1_572_864


@pytest.mark.parametrize("threshold", [0.5, 0.8, 1.0])
def test_cacheless_forward_is_the_reference_at_every_threshold(threshold):
    """The exit choice: at 0.5 and 0.8 the rows of one sequence leave at
    different passes (and later at 0.8 than at 0.5); at the published 1.0
    every row reads the last pass."""
    fields, cfg, params = make(early_exit_threshold=threshold)
    ids = jax.random.randint(jax.random.key(1), (2, 29), 1, 512)
    with jax.default_matmul_precision("highest"):
        z = T.forward(params, ids, cfg)
        _, lams = R.pass_states(params, ids[0], fields)
    for b in range(2):
        want = R.logits_at(params, ids[b], jnp.arange(29), fields, block=16)
        np.testing.assert_allclose(z[b], want, atol=2e-4)
    e = np.asarray(R.exit_steps(lams, threshold))
    if threshold == 1.0:
        assert set(e) == {PASSES - 1}
    else:
        assert len(set(e)) > 1
        assert np.all(e <= np.asarray(R.exit_steps(lams, 0.8)))
        assert np.all(np.asarray(R.exit_steps(lams, 0.5)) <= e)
    # the remainder: the exit distribution sums to one, the last pass
    # takes what is left
    p0 = np.asarray(lams[0])
    p1 = np.asarray(lams[1] * (1 - lams[0]))
    rest = np.asarray((1 - lams[0]) * (1 - lams[1]))
    np.testing.assert_allclose(p0 + p1 + rest, 1.0, atol=1e-6)


# ------------------------------------------------------- through the cache

def _sequence(prompt, z):
    toks = np.asarray(jnp.argmax(z, axis=-1))
    return np.concatenate([prompt, toks[:-1]]).astype(np.int32)


@pytest.mark.parametrize("n_prompt,kernel", [
    (23, False), (16, False), (23, True)],
    ids=["two-chunks-xla", "ends-on-a-chunk-xla", "two-chunks-kernels"])
def test_engine_prefill_then_decode_is_the_reference_on_logits(
        model, n_prompt, kernel):
    """Prefill in chunks of 16 (23 spans two, 16 ends ON one), then six
    decode steps through the pages of all nine caches (gather path, or both
    paged kernels interpreted), against the reference's whole forward pass
    of the same tokens.  float32 everywhere; 3e-4 catches a pass that reads
    another's cache, a norm left out, a wrong exit and not the summation
    order.  The device counters are the reference's exit steps."""
    fields, cfg, params = model
    prompt = np.random.default_rng(n_prompt).integers(
        1, 512, n_prompt).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        z, _, counted, _ = serve_logits(params, cfg, prompt, 7,
                                         kernel=kernel)
        seq = _sequence(prompt, z)
        pos = n_prompt - 1 + np.arange(7)
        want = R.logits_at(params, jnp.asarray(seq), jnp.asarray(pos),
                           fields, block=len(seq))
        _, lams = R.pass_states(params, jnp.asarray(seq), fields)
    np.testing.assert_allclose(z, want, atol=3e-4)
    e = np.asarray(R.exit_steps(lams, 0.5))[pos[1:]]   # the decode steps'
    assert tuple(counted) == (6 * PASSES, int(np.sum(e + 1)),
                              int(np.sum(e < PASSES - 1)))


def test_every_pass_of_every_token_fills_a_cache_of_its_own(model):
    """After a request of 23 + 4 tokens every one of the ``T x L`` caches
    holds a row for each of its 26 cached tokens and for no other (every
    pass of every token ran, whatever its exit), no two caches hold the
    same rows, and garbage in the pages that are NOT the request's and in
    another slot's rows changes nothing it is served."""
    _, cfg, params = model
    prompt = np.random.default_rng(23).integers(1, 512, 23).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        z0, bufs, _, mine = serve_logits(params, cfg, prompt, 4)
    assert len(bufs.k) == len(bufs.v) == PASSES * L
    rows = [np.asarray(a)[mine].reshape(64, -1) for a in bufs.k]
    for a in rows:
        written = np.any(a != 0, axis=1)
        assert written[:26].all() and not written[26:].any()
    for i in range(len(rows)):
        for j in range(i):
            assert np.abs(rows[i][:26] - rows[j][:26]).max() > 1e-3, (i, j)
    # the null page took the inactive slots' rows and nothing else was
    # written: a zeroed pool stays zero outside the request's pages
    others = np.setdiff1d(np.arange(1, 25), mine)
    assert not any(np.any(np.asarray(a)[others]) for a in bufs.k + bufs.v)
    pool = PagedKVPool(cfg, 25, 8)
    junk = jax.tree.map(
        lambda a: jax.random.normal(jax.random.key(7), a.shape, a.dtype),
        (pool.bufs.k, pool.bufs.v))
    with jax.default_matmul_precision("highest"):
        z1, dirty, _, _ = serve_logits(
            params, cfg, prompt, 4,
            bufs=pool.bufs._replace(k=junk[0], v=junk[1]))
    assert np.array_equal(np.asarray(z0), np.asarray(z1))
    for got, was in zip(dirty.k + dirty.v, junk[0] + junk[1]):
        assert np.array_equal(np.asarray(got)[others],
                              np.asarray(was)[others])


def _products_with_the_vocabulary(jaxpr, vocab: int) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general" \
                and eqn.outvars[0].aval.shape[-1:] == (vocab,):
            n += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _products_with_the_vocabulary(sub, vocab)
    return n


def test_the_head_runs_once_a_sampled_row(model):
    """Both engine programs multiply by the vocabulary ONCE (a prefill
    chunk's inside its ``cond``), not once a pass, and never norm ``h_e`` a
    second time on the seam."""
    _, cfg, params = model
    pool = PagedKVPool(cfg, 17, 8)
    i32 = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
    dec = jax.make_jaxpr(lambda *a: E._decode_core(*a, cfg=cfg))(
        pool.bufs, params, i32(2, 8), i32(2), i32(2), i32(2),
        jnp.ones((2,), bool), i32(3 + 4 * 2))
    pre = jax.make_jaxpr(lambda *a: E._prefill_core(*a, cfg=cfg))(
        pool.bufs, params, i32(1, 8), i32(1, 16), i32(), i32())
    assert _products_with_the_vocabulary(dec.jaxpr, 512) == 1
    assert _products_with_the_vocabulary(pre.jaxpr, 512) == 1
    x = jax.random.normal(jax.random.key(0), (2, 1, 64))
    assert LD.final_norm(x, params, cfg) is x


def test_the_engine_serves_the_reference_and_counts(model):
    """Five requests over two slots (a freed slot is granted again),
    prompts that span up to three chunks: every served token is the
    reference's greedy token, and the counters add up.  (Through the paged
    kernels: ``tests/benchmark/test_bench_loop_dense.py``.)"""
    fields, cfg, params = model
    eng = ServingEngine(params, cfg, max_batch=2, page_size=8,
                        max_seq_len=64, prefill_chunk=16)
    rng = np.random.default_rng(3)
    sizes = ((37, 6), (19, 3), (7, 9), (33, 2), (16, 5))
    reqs = [eng.submit(rng.integers(1, 512, size=n).astype(np.int32),
                       max_new_tokens=new) for n, new in sizes]
    eng.run()
    s = eng.stats
    for req, (_, new) in zip(reqs, sizes):
        assert len(req.tokens) == new
        assert req.tokens == reference_tokens("loop_dense", fields, params,
                                              req.prompt, req.tokens)
    assert s["admitted"] == 5 > eng.max_batch
    live = sum(new - 1 for _, new in sizes)
    assert s["ut_passes"] == PASSES * live
    assert live <= s["exit_step_sum"] < PASSES * live
    assert 0 < s["early_exit_rows"] <= live
    assert s["qkv_fused_layers"] == 0
    assert eng.retraces_after_warmup() == 0


def test_a_shared_prefix_is_shared_in_every_cache(model):
    """``prefix_cache``: the radix cache aliases PAGES, and a page holds a
    token's rows of all ``T x L`` caches under the one table, so a prompt
    prefix that was served is not prefilled again, in any pass, and the
    tokens are what the engine serves without the cache."""
    _, cfg, params = model
    rng = np.random.default_rng(9)
    shared = rng.integers(1, 512, size=24).astype(np.int32)
    prompts = [np.concatenate([shared, rng.integers(1, 512, size=n)])
               .astype(np.int32) for n in (5, 9)]

    def serve(**kw):
        eng = ServingEngine(params, cfg, max_batch=1, page_size=8,
                            max_seq_len=64, prefill_chunk=16, **kw)
        reqs = [eng.submit(p, max_new_tokens=4) for p in prompts]
        eng.run()
        return eng, [r.tokens for r in reqs]

    plain, want = serve()
    cached, got = serve(prefix_cache=True)
    assert got == want
    assert cached.prefix_cache.hit_pages == 3        # 24 shared tokens
    assert cached.stats["prefill_chunks"] < plain.stats["prefill_chunks"]


# ------------------------------------------------------------ the refusals

@pytest.mark.parametrize("kw,what", [
    ({"kv_quant": True}, "kv_quant"),
    ({"spec_k": 2, "draft_layers": 1}, "spec_k"),
    ({"flash_prefill": True}, "flash_prefill"),
    ({"disaggregate": True}, "disaggregate"),
    ({"mesh": "a mesh"}, "a tp mesh"),
])
def test_the_engine_refuses_what_is_not_built_for_the_block(model, kw, what):
    _, cfg, params = model
    with pytest.raises(NotImplementedError,
                       match=f"looped dense block.*"
                             f"ServingEngine with {what} is not built"):
        ServingEngine(params, cfg, **kw)


@pytest.mark.parametrize("name", ["fsdp", "tp", "pipeline", "generate",
                                  "init_cache", "layer_hook", "flops"])
def test_training_and_the_one_shot_decoder_refuse_the_block(model, name):
    import importlib
    gen = importlib.import_module(
        "distributed_training_sandbox_tpu.models.generate")
    from distributed_training_sandbox_tpu.parallel import (
        fsdp, pipeline, tensor)
    _, cfg, params = model
    ids = jnp.ones((1, 4), jnp.int32)
    call = {
        "fsdp": lambda: fsdp.make_fsdp_train_step(params, cfg, None),
        "tp": lambda: tensor.make_tp_train_step(params, cfg, None),
        "pipeline": lambda: pipeline.build_transformer_pipeline(params, cfg,
                                                                2),
        "generate": lambda: gen.generate(params, ids, cfg, max_new_tokens=2),
        "init_cache": lambda: gen.init_cache(cfg, 1, 8),
        "layer_hook": lambda: T.hidden_states(params, ids, cfg,
                                              layer_hook=lambda lw: lw),
        "flops": lambda: T.model_flops_per_token(cfg, 128),
    }[name]
    with pytest.raises(NotImplementedError,
                       match="looped dense block.*not built"):
        call()


@pytest.mark.parametrize("over,match", [
    ({"early_exit_threshold": 0.0}, r"must lie in \(0, 1\]"),
    ({"early_exit_threshold": 1.5}, r"must lie in \(0, 1\]"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings=False only"),
    ({"nope_interval": 4}, "nope_interval=0 only"),
    ({"intermediate_size": None}, "intermediate_size > 0"),
    ({"logits_scaling": 2.0}, "logits_scaling=1.0 only"),
    ({"total_ut_steps": 2, "partial_rotary_factor": 0.5},
     "partial_rotary_factor=1.0 only"),
    ({"num_key_value_heads": 3}, "a multiple of num_key_value_heads"),
])
def test_a_variant_the_block_does_not_build_is_refused_by_name(over, match):
    with pytest.raises(ValueError, match=match):
        T.TransformerConfig(**{**FIELDS, **over})


# ------------------------------------- for a TPU, at the published widths

def test_both_programs_lower_for_tpu_at_published_widths(monkeypatch):
    """The decode step and the prefill chunk at the cell's widths and pool
    shape (two weight layers, all four passes), lowered FOR a TPU on this
    host: the paged decode kernel and the flash prefill kernel are in them
    at group size 1, one call a (pass, layer)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    B, page, P = 8, 16, 40
    cfg = T.TransformerConfig(**{**CONFIG["fields"], "num_hidden_layers": 2,
                                 "dtype": jnp.bfloat16}, remat=False)
    sd = jax.ShapeDtypeStruct
    params = jax.eval_shape(lambda: T.init_params(jax.random.key(0), cfg))
    bufs = jax.eval_shape(lambda: PagedKVPool(cfg, B * P + 1, page).bufs)
    assert len(bufs.k) == 8
    i32 = lambda *shape: sd(shape, jnp.int32)  # noqa: E731
    dec = E.make_serve_decode_step(cfg, paged_kernel=True).trace(
        bufs, params, i32(B, P), i32(B), i32(B), i32(B), sd((B,), jnp.bool_),
        i32(3 + 4 * B)).lower(lowering_platforms=("tpu",)).as_text()
    pre = E.make_serve_prefill_step(cfg, paged_kernel=True).trace(
        bufs, params, i32(1, P), i32(1, 256), i32(),
        i32()).lower(lowering_platforms=("tpu",)).as_text()
    # identical calls are outlined: ONE Mosaic kernel a program, called
    # once a (pass, layer)
    import re
    for text, kernel in ((dec, "_decode_float"), (pre, "_prefill")):
        assert text.count("tpu_custom_call") == 1
        calls = [name for name in re.findall(r"call @(\w+)", text)
                 if name.startswith(kernel)]
        assert len(calls) == 8, set(re.findall(r"call @(\w+)", text))
