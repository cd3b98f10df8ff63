"""The latent-attention + held-experts block (``models/mla_moe.py``) on the
serving path, at a tiny size, float32, seeded weights, on the CPU: the
engine's own programs against the benchmark's plain reference on logits,
the absorbed form against the materialised one, the decode kernel
(interpret mode) against plain XLA, the expert shares against the uncut
layer, the pool's layout, and what is refused by name."""

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmarks.reference import mla_moe as R  # noqa: E402
from distributed_training_sandbox_tpu.models import mla_moe as M  # noqa: E402
from distributed_training_sandbox_tpu.models import transformer as T  # noqa: E402
from distributed_training_sandbox_tpu.serving import ServingEngine  # noqa: E402
from tests.serving_blocks import FIELDS as BLOCK_FIELDS  # noqa: E402
from distributed_training_sandbox_tpu.serving import engine as E  # noqa: E402
from distributed_training_sandbox_tpu.serving.kv_pool import (  # noqa: E402
    PagedKVPool, RadixPrefixCache, row_layout,
    token_row_bytes)

FIELDS = BLOCK_FIELDS["mla_moe"]


def attend_absorbed(qa, rows, vis, cfg):
    """Plain absorbed attention, the decode kernel's oracle: ``qa``
    (B, S, n, rank + rope) against the cache rows (B, K, >= rank + rope),
    ``vis`` (B, S, K) the keys a query row may see -> ``o~`` (B, S, n,
    rank) float32."""
    scale = 1.0 / np.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    s = jnp.einsum("bsnw,bkw->bnsk", qa, rows[..., :qa.shape[-1]],
                   preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(jnp.where(vis[:, None], s, -1e30), axis=-1)
    return jnp.einsum("bnsk,bkc->bsnc", p, rows[..., :cfg.kv_lora_rank],
                      preferred_element_type=jnp.float32)


def make(seed=0, scale=2.0, **over):
    fields = {**FIELDS, **over}
    cfg = T.TransformerConfig(**fields, dtype=jnp.float32, remat=False)
    params = jax.tree.map(lambda x: x * scale,
                          T.init_params(jax.random.key(seed), cfg))
    return fields, cfg, params


@pytest.fixture(scope="module")
def model():
    return make()


def test_one_dict_a_layer_and_the_count(model):
    _, cfg, params = model
    assert set(params) == {"embed", "layers", "final_norm", "lm_head"}
    dense, expert, last = params["layers"]
    assert dense["w_gate"].shape == (64, 160) and "w_router" not in dense
    assert expert["we_gate"].shape == (4, 64, 24) == last["we_gate"].shape
    assert expert["w_router"].shape == (64, 16) and "w_gate" not in expert
    assert expert["w_uq"].shape == (4 * (16 + 8), 48)       # out x in
    assert cfg.param_count() == sum(x.size for x in jax.tree.leaves(params))


def test_cacheless_forward_is_the_reference(model):
    """``T.forward`` (materialised, whole sequence) against the plain
    reference: float32 on both sides, so only the summation order differs
    (measured 8e-7 on logits of std 0.57)."""
    fields, cfg, params = model
    ids = jax.random.randint(jax.random.key(1), (2, 24), 1, 512)
    with jax.default_matmul_precision("highest"):
        z = T.forward(params, ids, cfg)
    for b in range(2):
        want = R.logits_at(params, ids[b], jnp.arange(24), fields, block=8)
        np.testing.assert_allclose(z[b], want, atol=2e-5)


def _serve_logits(params, cfg, prompt, n_new, *, kernel, chunk=8, page=8,
                  slots=3, slot=1, seq=64):
    """Chunked prefill and then decode of ONE request through the engine's
    own jitted cores (``_paged_forward`` is what ``_prefill_core`` and
    ``_decode_core`` run), tapped for logits: returns the (n_new, V) logits
    of the positions a server samples from, greedy tokens fed back."""
    P = seq // page
    pool = PagedKVPool(cfg, slots * P + 1, page)
    pages = np.zeros((slots, P), np.int32)
    pages[slot] = pool.allocator.alloc(P)
    bufs = pool.bufs

    @jax.jit
    def prefill(bufs, ids, pos, plen):
        apos = pos + jnp.arange(chunk, dtype=jnp.int32)[None, :]
        x, bufs, _ = E._paged_forward(params, ids, cfg, bufs,
                                      jnp.asarray(pages[slot:slot + 1]),
                                      apos, apos < plen)
        return E._all_logits(params, x, cfg), bufs

    @jax.jit
    def decode(bufs, toks, lengths, active):
        x, bufs, counts = E._paged_forward(
            params, toks[:, None], cfg, bufs, jnp.asarray(pages),
            lengths[:, None], active[:, None], paged_kernel=kernel)
        return E._last_logits(params, x, cfg), bufs, counts

    n = len(prompt)
    for pos in range(0, n, chunk):
        ids = np.zeros((1, chunk), np.int32)
        part = prompt[pos:pos + chunk]
        ids[0, :len(part)] = part
        z, bufs = prefill(bufs, jnp.asarray(ids), jnp.int32(pos),
                          jnp.int32(n))
    out = [z[0, (n - 1) % chunk]]
    active = np.zeros(slots, bool)
    active[slot] = True
    total = np.zeros(4, np.int64)
    for i in range(n_new - 1):
        toks = np.zeros(slots, np.int32)
        toks[slot] = int(jnp.argmax(out[-1]))
        lengths = np.zeros(slots, np.int32)
        lengths[slot] = n + i
        z, bufs, counts = decode(bufs, jnp.asarray(toks),
                                 jnp.asarray(lengths), jnp.asarray(active))
        out.append(z[slot])
        total += np.asarray(counts)
    return jnp.stack(out), total


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["xla-materialised", "kernel-absorbed"])
def test_engine_prefill_then_decode_is_the_reference_on_logits(model,
                                                               kernel):
    """Chunked prefill (three chunks, the last one partial) and six decode
    steps through the paged latent cache, with the decode attention as the
    materialised XLA loop and as the absorbed Pallas kernel (interpret
    mode), against the reference's full forward pass of the same tokens.
    float32 everywhere; the paths differ from the reference in summation
    order and in where ``w_uk``/``w_uv`` are applied, which float32 keeps
    under 1e-5 on logits of std 0.57 (measured 2e-6): 5e-5 catches a
    wrong position, page, mask, norm or weight and not the rounding."""
    fields, cfg, params = model
    prompt = np.random.default_rng(3).integers(1, 512, 21).astype(np.int32)
    n_new = 7
    with jax.default_matmul_precision("highest"):
        z, counts = _serve_logits(params, cfg, prompt, n_new, kernel=kernel)
    toks = np.asarray(jnp.argmax(z, axis=-1))
    seq = np.concatenate([prompt, toks[:-1]]).astype(np.int32)
    pos = len(prompt) - 1 + np.arange(n_new)
    want = R.logits_at(params, jnp.asarray(seq), jnp.asarray(pos), fields,
                       block=9)
    np.testing.assert_allclose(z, want, atol=5e-5)
    # one active row, 3 chosen experts, 2 expert layers, 6 decode steps
    assert counts[0] == 6 * 2 * 3 and counts[3] == 6 * 2
    assert 0 <= counts[2] <= counts[1] <= counts[0]


def test_absorbed_attention_is_the_materialised_one(model):
    _, cfg, params = model
    layer = params["layers"][1]
    B, S, K = 2, 3, 20
    k = jax.random.split(jax.random.key(5), 3)
    x = jax.random.normal(k[0], (B, S, cfg.hidden_size))
    pos = jnp.broadcast_to(jnp.arange(K - S, K), (B, S))
    cos, sin = E._ragged_rope_tables(pos, cfg.qk_rope_head_dim,
                                     cfg.rope_theta)
    q_nope, q_rope, _ = M.latent_qkv(x, layer, cfg=cfg, cos=cos, sin=sin)
    rows = jax.random.normal(k[1], (B, K, M.row_width(cfg)))
    vis = jnp.arange(K)[None, None, :] <= pos[:, :, None]
    with jax.default_matmul_precision("highest"):
        s, v = M._scores_and_values(q_nope, q_rope, rows, layer, cfg)
        p = jax.nn.softmax(jnp.where(vis[:, None], s, -1e30), axis=-1)
        want = jnp.einsum("bnsk,bkne->bsne", p, v)
        qa = M.absorb_queries(q_nope, q_rope, layer)
        got = M.unabsorb_values(attend_absorbed(qa, rows, vis, cfg),
                                layer, jnp.float32)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_latent_decode_kernel_is_plain_absorbed_attention():
    """The Pallas kernel (interpret mode) against ``attend_absorbed`` over
    the gathered rows: scattered pages, a table that is no multiple of the
    block, a length inside a page, a slot that holds nothing, and a pool
    whose rows end in padding."""
    from distributed_training_sandbox_tpu.ops.paged_attention import (
        paged_latent_attention_decode)
    B, n, rank, rope, page, P, W = 3, 4, 32, 8, 8, 19, 128
    k = jax.random.split(jax.random.key(0), 2)
    pool = jax.random.normal(k[0], (B * P + 1, page, W)) \
        .at[..., rank + rope:].set(0.0)
    qa = jax.random.normal(k[1], (B, n, rank + rope))
    pages = jnp.asarray(np.random.default_rng(0).permutation(
        np.arange(1, B * P + 1)).reshape(B, P), jnp.int32)
    lengths = jnp.asarray([17, 0, 150], jnp.int32)
    cfg = dataclasses.replace(make()[1], kv_lora_rank=rank,
                              qk_rope_head_dim=rope)
    scale = 1.0 / np.sqrt(cfg.qk_nope_head_dim + rope)
    got = paged_latent_attention_decode(qa, pool, pages, lengths, rank=rank,
                                        scale=scale, interpret=True)
    rows = pool[pages].reshape(B, P * page, W)
    vis = jnp.arange(P * page)[None, None, :] < lengths[:, None, None]
    want = attend_absorbed(qa[:, None], rows, vis, cfg)[:, 0]
    np.testing.assert_allclose(got[0], want[0], atol=2e-6)
    np.testing.assert_allclose(got[2], want[2], atol=2e-6)
    assert not np.asarray(got[1]).any()


def test_latent_decode_program_lowers_for_tpu_at_published_widths(
        monkeypatch):
    """The engine's decode program at the cell's shapes (published widths,
    one dense and one expert layer, bf16, 64 slots x 4,096 positions),
    lowered FOR a TPU on this host: attention is one Mosaic call a layer
    over the pool's padded rows, and nothing up-projects the view."""
    from distributed_training_sandbox_tpu.ops.paged_attention import (
        decode_kernel_takes)
    from distributed_training_sandbox_tpu.serving.kv_pool import PoolBuffers
    cfg = T.TransformerConfig(
        vocab_size=153600, hidden_size=7680, intermediate_size=18432,
        num_hidden_layers=2, num_attention_heads=128,
        num_key_value_heads=128, rope_theta=25.6e6, rms_norm_eps=1e-5,
        tie_word_embeddings=False, nope_interval=0, q_lora_rank=1536,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, first_k_dense_replace=1, moe_intermediate_size=2048,
        router_width=256, n_routed_experts=8, n_shared_experts=1,
        num_experts_per_tok=8, norm_topk_prob=True,
        routed_scaling_factor=2.5, sandwich_norm=True, dtype=jnp.bfloat16,
        remat=False)
    assert row_layout(cfg) == ((640,), False)        # 576, whole lane tiles
    assert decode_kernel_takes(cfg.dtype, cfg.kv_lora_rank, 16)
    B, P, page = 64, 256, 16
    sd = jax.ShapeDtypeStruct
    params = jax.eval_shape(lambda: T.init_params(jax.random.key(0), cfg))
    pool = tuple(sd((B * P + 1, page, 640), jnp.bfloat16) for _ in range(2))
    args = (PoolBuffers(k=pool, v=None, k_scale=None, v_scale=None), params,
            sd((B, P), jnp.int32), sd((B,), jnp.int32), sd((B,), jnp.int32),
            sd((B,), jnp.int32), sd((B,), jnp.bool_), sd((4,), jnp.int32))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = E.make_serve_decode_step(cfg, paged_kernel=True).trace(
        *args).lower(lowering_platforms=("tpu",)).as_text()
    # one jitted kernel call shared by both layers (``_decode_latent``)
    assert "tpu_custom_call" in text and text.count("_decode_latent") >= 3
    assert f"{B}x{P * page}x" not in text       # no gathered view


# ------------------------------------------------------------ the experts

def _layer_mlp(fields, params, li, r2):
    cfg = T.TransformerConfig(**fields, dtype=jnp.float32, remat=False)
    layer = params["layers"][li]
    assert M.is_expert_layer(li, cfg)
    with jax.default_matmul_precision("highest"):
        return M.expert_mlp(r2, layer, cfg=cfg)


def test_the_shares_add_up_to_the_uncut_layer():
    """32 experts split into 4 shares of 8: the routed parts that the four
    shares compute, plus the shared expert counted once, are the uncut
    reference's whole expert MLP; every share normalises its weights over
    all 8 chosen experts, held or not."""
    whole = dict(router_width=32, n_routed_experts=32, expert_offset=0,
                 num_experts_per_tok=8)
    fields, cfg, params = make(seed=7, **whole)
    r2 = jax.random.normal(jax.random.key(8), (2, 11, cfg.hidden_size))
    lw = params["layers"][1]
    with jax.default_matmul_precision("highest"):
        want = R._expert_mlp(r2.reshape(22, -1), lw, fields)
        shared = R._swiglu(r2.reshape(22, -1), lw["ws_gate"], lw["ws_up"],
                           lw["ws_down"])
    total, held = jnp.zeros_like(want), 0
    for share in range(4):
        part = {**fields, "n_routed_experts": 8, "expert_offset": 8 * share}
        cut = dict(params)
        cut["layers"] = tuple(
            {k: (v[8 * share:8 * share + 8] if k.startswith("we_") else v)
             for k, v in lw.items()} for lw in params["layers"])
        m, counts = _layer_mlp(part, cut, 1, r2)
        total = total + (m.reshape(22, -1) - shared)
        held += int(counts[1])
        assert int(counts[0]) == 22 * 8
    np.testing.assert_allclose(total + shared, want, atol=2e-5)
    assert held == 22 * 8           # every choice is held by exactly one


def test_no_token_is_dropped_when_every_choice_is_held_here():
    """All 8 choices of every row fall on the 8 held experts (the router
    has no other): 40 rows x 8 assignments, a load no capacity bucket of
    the older ``moe_mlp`` would take, all computed."""
    every = dict(router_width=8, n_routed_experts=8, expert_offset=0,
                 num_experts_per_tok=8)
    fields, cfg, params = make(seed=9, **every)
    r2 = jax.random.normal(jax.random.key(10), (1, 40, cfg.hidden_size))
    m, counts = _layer_mlp(fields, params, 2, r2)
    lw = params["layers"][2]
    with jax.default_matmul_precision("highest"):
        want = R._expert_mlp(r2[0], lw, fields)
    np.testing.assert_allclose(m[0], want, atol=2e-5)
    assert [int(c) for c in counts] == [320, 320, 8, 1]


def test_counters_leave_out_rows_that_hold_no_request(model):
    _, cfg, params = model
    layer = params["layers"][1]
    r2 = jax.random.normal(jax.random.key(11), (5, 1, cfg.hidden_size))
    valid = jnp.asarray([[True], [False], [True], [False], [False]])
    _, counts = M.expert_mlp(r2, layer, cfg=cfg, valid=valid)
    _, alone = M.expert_mlp(r2[jnp.asarray([0, 2])], layer, cfg=cfg)
    assert [int(c) for c in counts] == [int(c) for c in alone]
    assert int(counts[0]) == 2 * cfg.num_experts_per_tok


# ---------------------------------------------------------------- the pool

def test_the_pool_holds_one_padded_row_a_token_and_no_v(model):
    _, cfg, _ = model
    pool = PagedKVPool(cfg, 9, 8)
    assert pool.bufs.v is None and pool.bufs.k_scale is None
    assert len(pool.bufs.k) == cfg.num_hidden_layers
    # 32 + 8 = 40 columns, padded to one 128-lane tile
    assert pool.bufs.k[0].shape == (9, 8, 128)
    assert pool.row_bytes == token_row_bytes(cfg) == 128 * 4
    assert pool.spec.v is None and len(pool.spec.k) == 3
    dense = T.TINY_LM
    assert row_layout(dense) == ((2, 16), True)
    assert token_row_bytes(dense) == 2 * 2 * 16 * 4
    assert token_row_bytes(dense, kv_quant=True) == 2 * 2 * 16 + 2 * 2 * 4
    with pytest.raises(NotImplementedError, match="int8 pool of latent"):
        PagedKVPool(cfg, 9, 8, kv_quant=True)


def test_pages_and_the_prefix_trie_do_not_look_inside_a_row(model):
    """``PageAllocator`` and ``RadixPrefixCache`` are page-granular: the
    same grants, frees, matches, swaps and evictions over the latent pool
    as over a dense one."""
    _, cfg, _ = model
    logs = []
    for c in (cfg, T.TINY_LM):
        pool = PagedKVPool(c, 17, 4)
        alloc, log = pool.allocator, []
        trie = RadixPrefixCache(alloc, 4)
        a, b = alloc.alloc(5), alloc.alloc(3)
        log += [a, b, alloc.alloc(20), alloc.free_pages]
        toks = list(range(100, 118))
        nodes, swaps = trie.insert(toks, a, [])
        log += [len(nodes), swaps, trie.cached_pages]
        hit = trie.match(toks[:9] + [7, 7, 7])
        log += [[n.page for n in hit]]
        trie.acquire(hit)
        twin, swaps = trie.insert(toks, alloc.alloc(5), hit)
        log += [sorted(swaps.items()), alloc.free_pages]
        trie.release(twin)
        trie.release(nodes)
        log += [trie.reclaimable_pages, trie.evict(3), alloc.free_pages,
                alloc.pages_in_use, round(pool.utilization, 4)]
        alloc.free(b)
        log += [alloc.free_pages]
        logs.append(log)
    assert logs[0] == logs[1]


# ---------------------------------------------------------- through the engine

def test_the_engine_serves_the_block_and_counts_its_routing(model):
    fields, cfg, params = model
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 512, size=n).astype(np.int32)
               for n in (5, 19, 33, 12)]
    served = {}
    for kernel in (False, True):
        eng = ServingEngine(params, cfg, max_batch=3, page_size=8,
                            max_seq_len=64, prefill_chunk=16,
                            paged_kernel=kernel, hbm_budget_gb=1.0)
        reqs = [eng.submit(p, max_new_tokens=5) for p in prompts]
        eng.run()
        served[kernel] = [r.tokens for r in reqs]
        s = eng.stats
        assert s["decode_inplace_steps"] == (s["decode_steps"] if kernel
                                             else 0)
        assert s["moe_expert_layer_steps"] == 2 * s["decode_steps"]
        # every decode step of every request routed 3 of 16, 2 layers
        assert s["moe_assignments"] == 4 * 4 * 2 * 3
        assert 0 < s["moe_experts_touched"] <= s["moe_assignments_held"] \
            < s["moe_assignments"]
        assert eng.retraces_after_warmup() == 0
        assert eng.slo_report()["pool"]["bytes_per_token"] == 3 * 128 * 4
    for r, toks in zip(reqs, served[True]):
        seq = np.concatenate([r.prompt, np.asarray(toks[:-1], np.int32)])
        z = R.logits_at(params, jnp.asarray(seq),
                        jnp.asarray(r.n_prompt - 1 + np.arange(5)), fields,
                        block=8)
        assert list(np.asarray(jnp.argmax(z, -1))) == toks
    assert served[False] == served[True]


@pytest.mark.parametrize("kw,what", [
    ({"kv_quant": True}, "kv_quant"),
    ({"spec_k": 2, "draft_layers": 1}, "spec_k"),
    ({"flash_prefill": True}, "flash_prefill"),
    ({"disaggregate": True}, "disaggregate"),
    ({"prefix_cache": True}, "prefix_cache"),
    ({"mesh": "a mesh"}, "a tp mesh"),
])
def test_the_engine_refuses_what_is_not_built_for_the_block(model, kw, what):
    _, cfg, params = model
    with pytest.raises(NotImplementedError, match=f"ServingEngine with "
                                                  f"{what} is not built"):
        ServingEngine(params, cfg, **kw)


@pytest.mark.parametrize("name", [
    "fsdp", "fsdp_auto", "sp", "tp", "pipeline", "moe_lm", "composable",
    "generate", "init_cache", "layer_hook"])
def test_training_and_the_one_shot_decoder_refuse_the_block(model, name):
    import importlib
    G = importlib.import_module(      # the package re-exports a function
        "distributed_training_sandbox_tpu.models.generate")   # of that name
    from distributed_training_sandbox_tpu.parallel import (
        composable, expert, fsdp, pipeline, sequence, tensor)
    _, cfg, params = model
    ids = jnp.ones((1, 4), jnp.int32)
    call = {
        "fsdp": lambda: fsdp.make_fsdp_train_step(params, cfg, None),
        "fsdp_auto": lambda: fsdp.make_fsdp_auto_train_step(params, cfg,
                                                            None),
        "sp": lambda: sequence.make_sp_train_step(params, cfg, None),
        "tp": lambda: tensor.make_tp_train_step(params, cfg, None),
        "pipeline": lambda: pipeline.build_transformer_pipeline(params, cfg,
                                                                2),
        "moe_lm": lambda: expert.make_moe_lm_train_step(params, cfg, None),
        "composable": lambda: composable.make_composable_train_step(
            params, None, None, model_cfg=cfg),
        "generate": lambda: G.generate(params, ids, cfg, max_new_tokens=2),
        "init_cache": lambda: G.init_cache(cfg, 1, 8),
        "layer_hook": lambda: T.hidden_states(params, ids, cfg,
                                              layer_hook=lambda lw: lw),
    }[name]
    with pytest.raises(NotImplementedError, match="not built for it"):
        call()


@pytest.mark.parametrize("over,match", [
    ({"sandwich_norm": False}, "sandwich_norm=True only"),
    ({"norm_topk_prob": False}, "norm_topk_prob=True only"),
    ({"nope_interval": 4}, "nope_interval=0 only"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings=False only"),
    ({"q_lora_rank": 0}, r"needs \['q_lora_rank'\]"),
    ({"expert_offset": 14}, "not among the router's 16"),
    ({"num_experts_per_tok": 17}, "exceeds router_width"),
    ({"first_k_dense_replace": 9}, "first_k_dense_replace must lie in"),
    ({"qk_rope_head_dim": 7}, "qk_rope_head_dim must be even"),
    ({"n_experts": 4}, "n_experts=0 only"),
    ({"num_experts": 4}, "num_experts=0 only"),
    ({"attention_impl": "flash"}, "attention_impl='xla' only"),
])
def test_a_variant_the_block_does_not_build_is_refused_by_name(over, match):
    with pytest.raises(ValueError, match=match):
        T.TransformerConfig(**{**FIELDS, **over})
