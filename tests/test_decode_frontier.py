"""Decode-speed-frontier suite (PR 18): the radix prefix cache's
trie/refcount/CoW/eviction bookkeeping, speculative decoding's
acceptance + rollback arithmetic, the Pallas flash prefill kernel's two
parity tiers, and THE law extended to all three legs — every request
served with any combination of prefix caching, speculation, and flash
prefill stays BITWISE identical to one-shot greedy ``generate`` across
tp / int8-KV / paged-kernel configs — plus the planner's draft-model
terms, the speculative knob axes, and the router's cache-hit prior."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_sandbox_tpu.models import transformer as T
from distributed_training_sandbox_tpu.models.generate import generate
from distributed_training_sandbox_tpu.serving import (
    PageAllocator, RadixPrefixCache, ServingEngine, pool_capacity_pages,
    serve_waterline_gb)

pytestmark = pytest.mark.serving


def _chaotic_params(cfg, seed=0, scale=3.0):
    params = T.init_params(jax.random.PRNGKey(seed), cfg)
    return jax.tree.map(lambda x: (x * scale).astype(x.dtype), params)


def _prompts_with_shared_prefix(cfg, n, sys_len=17, seed=7):
    rng = np.random.default_rng(seed)
    head = rng.integers(1, cfg.vocab_size, size=sys_len).astype(np.int32)
    return [np.concatenate([head, rng.integers(
        1, cfg.vocab_size, size=int(rng.integers(3, 12))).astype(
            np.int32)]) for _ in range(n)]


def _assert_parity(eng, reqs, params, cfg, max_new, kv_quant=False):
    for r in reqs:
        ref = np.asarray(generate(
            params, r.prompt[None], cfg, max_new_tokens=max_new,
            kv_quant=kv_quant, cache_capacity=eng.view_capacity))[0]
        got = np.asarray(r.tokens, np.int32)
        assert got.shape == ref.shape and (got == ref).all(), \
            f"rid {r.rid}: {got.tolist()} != {ref.tolist()}"


# ---- radix trie bookkeeping ---------------------------------------------

def test_radix_trie_match_insert_release_evict():
    alloc = PageAllocator(16)
    cache = RadixPrefixCache(alloc, page_size=4)
    toks = np.arange(100, 113, dtype=np.int32)      # 13 tokens
    pages = alloc.alloc(3)
    # 13 tokens -> 3 full pages cached ((13-1)//4: the last prompt
    # position always prefills for the first-token logits)
    nodes, swaps = cache.insert(toks, pages, [])
    assert len(nodes) == 3 and not swaps
    assert cache.cached_pages == 3
    assert [n.page for n in cache.match(toks)] == pages
    # divergence in the second chunk -> only the first page matches
    div = toks.copy()
    div[5] += 1
    assert len(cache.match(div)) == 1
    # insert holds one ref per node; pages can't be evicted until freed
    assert cache.evict(3) == 0
    cache.release(nodes)
    with pytest.raises(ValueError):
        cache.release(nodes)            # refcount underflow is loud
    free_before = alloc.free_pages
    assert cache.evict(2) == 2          # leaf-first LRU
    assert cache.cached_pages == 1
    assert alloc.free_pages == free_before + 2


def test_radix_eviction_respects_inflight_refcounts():
    alloc = PageAllocator(16)
    cache = RadixPrefixCache(alloc, page_size=4)
    toks = np.arange(1, 14, dtype=np.int32)
    pages = alloc.alloc(3)
    nodes, _ = cache.insert(toks, pages, [])
    cache.release(nodes)
    # a new request aliases the first two pages and is in flight
    held = cache.match(toks)[:2]
    cache.acquire(held)
    assert cache.evict(10) == 1         # only the refcount-0 leaf goes
    assert cache.cached_pages == 2
    # interior nodes are never evicted from under their children: the
    # held chain keeps both remaining pages resident
    cache.release(held)
    assert cache.evict(10) == 2
    assert cache.cached_pages == 0
    assert alloc.free_pages == 15       # everything back in the pool


def test_radix_concurrent_twin_insert_swaps_to_cached_pages():
    alloc = PageAllocator(16)
    cache = RadixPrefixCache(alloc, page_size=4)
    toks = np.arange(1, 14, dtype=np.int32)
    pages_a = alloc.alloc(3)
    pages_b = alloc.alloc(3)
    nodes_a, swaps_a = cache.insert(toks, pages_a, [])
    assert not swaps_a
    # a twin that prefilled the same prefix concurrently (admitted
    # before A's insert): its duplicate pages are freed, the cached
    # twin's pages adopted — contents are bitwise-identical
    free_before = alloc.free_pages
    nodes_b, swaps_b = cache.insert(toks, pages_b, [])
    assert swaps_b == {i: pages_a[i] for i in range(3)}
    assert [n.page for n in nodes_b] == pages_a
    assert alloc.free_pages == free_before + 3
    assert cache.cached_pages == 3


# ---- copy-on-write: aliased pages are never mutated ---------------------

def test_cow_aliased_pages_stay_byte_identical():
    """Two requests share a 2-page prefix then diverge: the second
    aliases the cached pages, writes only its own, and the shared
    pages' bytes never change — with both streams bitwise-exact."""
    cfg = T.TINY_LM
    params = _chaotic_params(cfg)
    eng = ServingEngine(params, cfg, max_batch=2, page_size=8,
                        max_seq_len=48, prefill_chunk=8,
                        prefix_cache=True)
    rng = np.random.default_rng(3)
    head = rng.integers(1, cfg.vocab_size, size=17).astype(np.int32)
    p1 = np.concatenate([head, rng.integers(
        1, cfg.vocab_size, size=5).astype(np.int32)])
    r1 = eng.submit(p1, max_new_tokens=6)
    eng.run()
    cached = np.array(sorted(n.page for n in eng.prefix_cache._nodes))
    assert len(cached) == 2             # (22-1)//8 full pages
    snap = [np.asarray(eng.pool.bufs.k[layer])[cached]
            for layer in range(cfg.num_hidden_layers)]
    # diverge right AT the aliased boundary: same 16-token prefix,
    # different continuation — CoW must leave the aliased pages alone
    p2 = np.concatenate([head, rng.integers(
        1, cfg.vocab_size, size=9).astype(np.int32)])
    r2 = eng.submit(p2, max_new_tokens=6)
    eng.run()
    assert eng.prefix_cache.hit_pages == 2
    for layer in range(cfg.num_hidden_layers):
        now = np.asarray(eng.pool.bufs.k[layer])[cached]
        assert (now == snap[layer]).all(), \
            f"aliased page mutated in layer {layer}"
    _assert_parity(eng, [r1, r2], params, cfg, 6)
    assert eng.retraces_after_warmup() == 0


def test_radix_eviction_under_pool_pressure_end_to_end():
    """A pool sized for ~2 resident requests: the trie keeps retired
    prefixes until admission pressure forces eviction, and everything
    stays bitwise through the churn."""
    cfg = T.TINY_LM
    params = _chaotic_params(cfg, seed=2)
    eng = ServingEngine(params, cfg, max_batch=2, page_size=8,
                        max_seq_len=40, n_pages=11, prefill_chunk=8,
                        prefix_cache=True)
    rng = np.random.default_rng(5)
    prompts = []
    for i in range(4):                  # 4 distinct 2-page prefixes
        head = rng.integers(1, cfg.vocab_size, size=17).astype(np.int32)
        prompts.append(np.concatenate([head, rng.integers(
            1, cfg.vocab_size, size=4).astype(np.int32)]))
    reqs = [eng.submit(p, max_new_tokens=5) for p in prompts]
    eng.run()
    st = eng.prefix_cache.stats()
    assert st["evictions"] > 0          # pressure actually evicted
    # every non-cached page went back to the free list: what's still
    # in use is exactly what the trie owns (cached pages live OUTSIDE
    # the free list), and the ledger closes over the 10 usable pages
    assert eng.pool.allocator.pages_in_use \
        == eng.prefix_cache.cached_pages
    assert eng.pool.allocator.free_pages \
        + eng.prefix_cache.cached_pages == 10
    _assert_parity(eng, reqs, params, cfg, 5)
    assert eng.retraces_after_warmup() == 0


# ---- speculative decoding ----------------------------------------------

def test_spec_accept_core_bookkeeping():
    """The device-side accept rule: longest matching prefix + 1 bonus
    token, clamped at stop, frozen for inactive slots."""
    from distributed_training_sandbox_tpu.serving.engine import (
        _spec_accept_core)
    k = 3
    toks_blk = jnp.array([[5, 7, 8, 9],     # proposals match 2 then miss
                          [5, 7, 8, 9],     # all match
                          [1, 2, 3, 4],     # first proposal misses
                          [1, 2, 3, 4]])    # inactive slot
    greedy = jnp.array([[7, 8, 6, 6],       # row 0: d1=7 ok d2=8 ok d3!=9
                        [7, 8, 9, 2],       # row 1: full match -> e=4
                        [9, 9, 9, 9],       # row 2: miss -> e=1
                        [9, 9, 9, 9]])
    toks = jnp.array([5, 5, 1, 1])
    lengths = jnp.array([10, 10, 10, 10])
    stop_at = jnp.array([20, 12, 20, 20])   # row 1 clamps 4 -> 2
    active = jnp.array([True, True, True, False])
    nxt, new_len, new_active, e = _spec_accept_core(
        toks_blk, greedy, toks, lengths, stop_at, active)
    assert e.tolist() == [3, 2, 1, 0]
    assert new_len.tolist() == [13, 12, 11, 10]
    # next committed token = greedy[e-1]; inactive slots keep theirs
    assert nxt.tolist() == [6, 8, 9, 1]
    assert new_active.tolist() == [True, False, True, False]


def test_spec_draft_equals_target_accepts_everything():
    """draft_layers == the full target stack makes the draft the
    target: every proposal is the target's own greedy token, so
    acceptance is ~1 and decode steps per token collapse."""
    cfg = T.TINY_LM
    # scale 1.5: still discriminating, but not so chaotic that the
    # draft's S=1 step and the verify's S=k+1 step disagree at the ulp
    params = _chaotic_params(cfg, seed=4, scale=1.5)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)
               for n in (5, 11)]
    eng = ServingEngine(params, cfg, max_batch=2, page_size=8,
                        max_seq_len=48, spec_k=3,
                        draft_layers=cfg.num_hidden_layers)
    reqs = [eng.submit(p, max_new_tokens=9) for p in prompts]
    eng.run()
    slo = eng.slo_report()
    sp = slo["speculative"]
    # rejections can only come from the stop clamp, not mismatches
    assert sp["acceptance_rate"] > 0.6
    assert slo["scheduler"]["decode_steps_per_token"] < 1.0
    _assert_parity(eng, reqs, params, cfg, 9)
    assert eng.retraces_after_warmup() == 0


def test_spec_rollback_with_shallow_draft_stays_bitwise():
    """A 1-layer draft disagrees constantly (chaotic weights): nearly
    every burst rolls back proposed tails, and the emitted streams are
    still exactly vanilla greedy — the rollback bookkeeping law."""
    cfg = T.TINY_LM
    params = _chaotic_params(cfg, seed=6)
    rng = np.random.default_rng(13)
    prompts = [rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)
               for n in (4, 13)]
    eng = ServingEngine(params, cfg, max_batch=2, page_size=8,
                        max_seq_len=48, spec_k=3, draft_layers=1,
                        sync_every=2)
    reqs = [eng.submit(p, max_new_tokens=7) for p in prompts]
    eng.run()
    sp = eng.slo_report()["speculative"]
    assert sp["proposed"] > sp["accepted"]      # real rollbacks happened
    _assert_parity(eng, reqs, params, cfg, 7)
    assert eng.retraces_after_warmup() == 0


# ---- flash prefill kernel ----------------------------------------------

def _flash_fixture(seed=0, B=3, S=8, P=4, page=4, nkv=2, rep=2, hd=8):
    """A pool, a table and one chunk a batch row, each at a start of its
    own (the kernel takes a chunk: consecutive positions)."""
    rng = np.random.default_rng(seed)
    n_pages = B * P + 1
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    qg = f(B, S, nkv, rep, hd)
    pk, pv = f(n_pages, page, nkv, hd), f(n_pages, page, nkv, hd)
    pages = jnp.asarray(np.arange(1, B * P + 1, dtype=np.int32)
                        .reshape(B, P))
    starts = rng.integers(0, P * page - S + 1, size=(B, 1))
    apos = jnp.asarray(starts + np.arange(S)[None], jnp.int32)
    return qg, pk, pv, pages, apos


@jax.jit
def _flash_reference(qg, pk, pv, pages, apos):
    """The engine's gather+einsum prefill attention, op for op."""
    B, S = qg.shape[:2]
    V = pages.shape[1] * pk.shape[1]
    gk = pk[pages].reshape(B, V, *pk.shape[2:])
    gv = pv[pages].reshape(B, V, *pv.shape[2:])
    scores = jnp.einsum("bsgrh,bkgh->bgrsk", qg, gk,
                        preferred_element_type=jnp.float32) \
        / np.sqrt(qg.shape[-1])
    vis = jnp.arange(V)[None, None, :] <= apos[:, :, None]
    scores = jnp.where(vis[:, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bgrsk,bkgh->bsgrh", probs.astype(jnp.float32),
                      gv, preferred_element_type=jnp.float32)


def test_flash_prefill_single_block_equals_the_gather_path():
    """A table of no more pages than one DMA block: the online softmax
    runs once, and what is left against the gather path is the divide
    at the end and float32 summation order (the old kernel's bitwise
    tier went with its whole-view tile)."""
    from distributed_training_sandbox_tpu.ops import flash_prefill as F
    qg, pk, pv, pages, apos = _flash_fixture()
    assert pages.shape[1] <= F.PAGES_PER_BLOCK
    ref = np.asarray(_flash_reference(qg, pk, pv, pages, apos))
    out = np.asarray(F.paged_flash_prefill(qg, pk, pv, pages, apos,
                                           probs_dtype=jnp.float32,
                                           interpret=True))
    assert out.shape == ref.shape and out.dtype == np.float32
    np.testing.assert_allclose(out, ref, rtol=0,
                               atol=1e-6 * np.abs(ref).max())


@pytest.mark.parametrize("block_pages", [1, 2, 3])
def test_flash_prefill_multi_block_online_softmax_allclose(monkeypatch,
                                                           block_pages):
    """Several blocks a slot, also where the block does not divide the
    4-page table: the running maximum, the rescaled sum and accumulator
    across blocks give the gather path's value."""
    from distributed_training_sandbox_tpu.ops import flash_prefill as F
    monkeypatch.setattr(F, "PAGES_PER_BLOCK", block_pages)
    qg, pk, pv, pages, apos = _flash_fixture(seed=1)
    ref = np.asarray(_flash_reference(qg, pk, pv, pages, apos))
    out = np.asarray(F.paged_flash_prefill(
        qg, pk, pv, pages, apos, probs_dtype=jnp.float32, interpret=True))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


def test_flash_prefill_rejects_int8_and_shapes_a_tpu_refuses():
    from distributed_training_sandbox_tpu.ops.flash_prefill import (
        paged_flash_prefill)
    qg, pk, pv, pages, apos = _flash_fixture()
    with pytest.raises(ValueError, match="float-pool"):
        paged_flash_prefill(qg, pk.astype(jnp.int8), pv.astype(jnp.int8),
                            pages, apos, interpret=True)
    # compiled (not interpreted), the tiny head_dim is named, not tried
    with pytest.raises(ValueError, match="prefill_kernel_takes"):
        paged_flash_prefill(qg, pk, pv, pages, apos, interpret=False)
    with pytest.raises(ValueError, match="flash"):
        ServingEngine(_chaotic_params(T.TINY_LM), T.TINY_LM,
                      flash_prefill=True, kv_quant=True)


# ---- the parity matrix: all legs x tp / kv-quant / paged-kernel ---------

_ALL_LEGS = dict(prefix_cache=True, spec_k=2, draft_layers=1,
                 flash_prefill=True)


@pytest.mark.parametrize("legs,base", [
    # each leg alone on the plain base, then the full stack against
    # every base config — kv_quant runs cache+spec (flash is float-pool
    # only, pinned by test_flash_prefill_rejects_int8_and_shapes_a_tpu_refuses)
    (dict(prefix_cache=True), "plain"),
    (dict(spec_k=2, draft_layers=1), "plain"),
    (dict(flash_prefill=True), "plain"),
    (_ALL_LEGS, "plain"),
    (_ALL_LEGS, "tp"),
    (_ALL_LEGS, "paged_kernel"),
    (dict(prefix_cache=True, spec_k=2, draft_layers=1), "kv_quant"),
], ids=["cache", "spec", "flash", "all", "all-tp", "all-paged",
        "cache+spec-kvq"])
def test_frontier_parity_matrix(legs, base):
    """Every leg combination x every engine base config: bitwise vs
    one-shot generate, zero retraces."""
    cfg = T.TINY_LM
    params = _chaotic_params(cfg, seed=1)
    kw = dict(legs)
    kv_quant = base == "kv_quant"
    if kv_quant:
        kw["kv_quant"] = True
    if base == "paged_kernel":
        kw["paged_kernel"] = True
    if base == "tp":
        from distributed_training_sandbox_tpu.utils import make_mesh
        kw["mesh"] = make_mesh({"dp": len(jax.devices()) // 2, "tp": 2},
                               register=False)
    prompts = _prompts_with_shared_prefix(cfg, 4)
    eng = ServingEngine(params, cfg, max_batch=2, page_size=8,
                        max_seq_len=48, prefill_chunk=8, **kw)
    reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.run()
    if legs.get("prefix_cache"):
        assert eng.prefix_cache.hit_pages > 0   # the prefix was shared
    _assert_parity(eng, reqs, params, cfg, 6, kv_quant=kv_quant)
    assert eng.retraces_after_warmup() == 0


# ---- planner, knobs, router, trace -------------------------------------

def test_accounting_prices_draft_weights_and_pool():
    from distributed_training_sandbox_tpu.serving import make_draft_params
    cfg = T.TINY_LM
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    _, dcfg = make_draft_params(params, cfg, 2)
    base = serve_waterline_gb(cfg, 64, 8, weight_bytes=1 << 20)
    spec = serve_waterline_gb(cfg, 64, 8, weight_bytes=1 << 20,
                              draft_weight_bytes=1 << 19, draft_cfg=dcfg)
    # draft terms are strictly additive: weights + the mirrored pool
    assert spec > base
    n_base = pool_capacity_pages(cfg, 8, budget_gb=0.5)
    n_spec = pool_capacity_pages(cfg, 8, budget_gb=0.5,
                                 draft_weight_bytes=1 << 19,
                                 draft_cfg=dcfg)
    assert 0 < n_spec < n_base
    # the inverse law holds with the draft resident
    assert serve_waterline_gb(cfg, n_spec, 8, draft_weight_bytes=1 << 19,
                              draft_cfg=dcfg) <= 0.5 * 0.9 + 1e-9


def test_serving_knob_space_grows_spec_axes():
    from distributed_training_sandbox_tpu.tuner.knobs import (
        ServingKnobSpace)
    space = ServingKnobSpace()
    axes = space.axes()
    assert "spec_k" in axes and "draft_layers" in axes
    cands = space.enumerate()
    assert all("spec_k" in c and "draft_layers" in c for c in cands)
    # spec_k=0 doesn't fan out over draft depths (no duplicate vanilla)
    zero = [c for c in cands if c["spec_k"] == 0]
    assert len({tuple(sorted(c.items())) for c in zero}) == len(zero)
    assert len({c["draft_layers"] for c in zero}) == 1
    clone = ServingKnobSpace.from_axes(axes)
    assert clone == space and clone.space_hash() == space.space_hash()
    assert ServingKnobSpace(spec_k=(0, 8)).space_hash() \
        != space.space_hash()


def test_admission_prior_discounts_on_cache_hits():
    from distributed_training_sandbox_tpu.serving import (
        AdmissionController)
    a = AdmissionController(4, burst_s=0.1)
    _, ttft0, _ = a.offer(0.0, 8)
    b = AdmissionController(4, burst_s=0.1)
    for _ in range(20):
        b.note_cache_hit_rate(0.8)
    _, ttft1, _ = b.offer(0.0, 8)
    assert ttft1 < ttft0                # hits shrink the modeled TTFT
    # and the uncalibrated controller stays deterministic
    c = AdmissionController(4, burst_s=0.1, calibrate=False)
    c.note_cache_hit_rate(0.8)
    assert c.cache_hit_rate == 0.0


def test_tenant_trace_is_seed_reproducible():
    from scripts.serve_bench import build_trace
    mk = lambda: build_trace(np.random.default_rng(42), 24, 8.0, 512,
                             96, tenants=3, overlap_frac=0.7,
                             sys_len=24)
    t1, t2 = mk(), mk()
    assert len(t1) == len(t2) == 24
    from collections import Counter
    heads = Counter()
    for (a1, p1, n1), (a2, p2, n2) in zip(t1, t2):
        assert a1 == a2 and n1 == n2 and (p1 == p2).all()
        if len(p1) >= 24:
            heads[tuple(p1[:24])] += 1
    # the skew is real: repeated heads collapse onto <= 3 tenant system
    # prompts (one-off long heads are the bimodal document tail)
    repeated = [h for h, c in heads.items() if c >= 2]
    assert 1 <= len(repeated) <= 3
    assert sum(heads[h] for h in repeated) >= 24 * 0.7 * 0.5
