"""The block of sliding-window and full attention layers over held experts
(``models/swa_moe.py``) on the serving path, at a tiny size, float32, seeded
weights, on the CPU: the cache-less forward and the engine's own programs
through BOTH page classes against the benchmark's plain reference on logits
(contexts under, at and far over the window; a chunk that divides the window
and one that does not; gather path and the paged kernels interpreted); a
ring one page too short fails the same comparison; the kernels' lower bound
against plain attention; the shares of an expert layer against the uncut
layer; the selection bias; the two allocators through admission,
retirement and failover; the pools' shapes; the counters and the grants on
``serve/admit``; what is refused by name; and both programs lowered for a
TPU at the published widths."""

import functools
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmarks.reference import swa_moe as R  # noqa: E402
from distributed_training_sandbox_tpu.models import mla_moe as M  # noqa: E402
from distributed_training_sandbox_tpu.models import swa_moe as W  # noqa: E402
from distributed_training_sandbox_tpu.models import transformer as T  # noqa: E402
from distributed_training_sandbox_tpu.ops.flash_prefill import (  # noqa: E402
    paged_flash_prefill)
from distributed_training_sandbox_tpu.ops.paged_attention import (  # noqa: E402
    paged_attention_decode)
from distributed_training_sandbox_tpu.serving import ServingEngine  # noqa: E402
from distributed_training_sandbox_tpu.serving import engine as E  # noqa: E402
from distributed_training_sandbox_tpu.serving.kv_pool import (  # noqa: E402
    PageAllocator, PagedKVPool, ring_pages, ring_view)
from distributed_training_sandbox_tpu.serving.scheduler import (  # noqa: E402
    ContinuousBatcher, Request)
from tests.serving_blocks import FIELDS as BLOCK_FIELDS  # noqa: E402

#: the published pattern at a tiny size: five layers, the first dense, the
#: fourth attending its whole context, a window of 16
FIELDS = {**BLOCK_FIELDS["swa_moe"], "num_hidden_layers": 5,
          "global_attn_every_n_layers": 4, "sliding_window": 16}
WINDOW, PAGE = 16, 4


def make(seed=0, scale=3.0, **over):
    """Seeded weights, scaled as the benchmark scales them, the norms'
    weights moved off their init of 1."""
    fields = {**FIELDS, **over}
    cfg = T.TransformerConfig(**fields, dtype=jnp.float32, remat=False)
    params = jax.tree.map(lambda x: x * scale,
                          T.init_params(jax.random.key(seed), cfg))
    key = jax.random.key(seed + 100)

    def off_one(path, x):
        if "norm" in str(path[-1]) or "ln" in str(path[-1]):
            k = jax.random.fold_in(key, sum(map(ord, str(path))))
            return 1.0 + 0.3 * jax.random.normal(k, x.shape, x.dtype)
        return x

    return fields, cfg, jax.tree_util.tree_map_with_path(off_one, params)


@pytest.fixture(scope="module")
def model():
    return make()


def test_the_block_is_selected_and_counted(model):
    _, cfg, params = model
    assert cfg.swa_moe and not (cfg.mla_moe or cfg.gdn_hybrid or cfg.gdn_moe)
    assert cfg.block_module is W and cfg.held_experts == 4
    assert W.layer_kinds(cfg) == ("window",) * 3 + ("full", "window")
    assert [W.is_expert_layer(li, cfg) for li in range(5)] \
        == [False, True, True, True, True]
    dense, expert = params["layers"][0], params["layers"][3]
    for lw in (dense, expert):
        assert lw["wq"].shape == lw["wg"].shape == (64, 64)
        assert lw["wk"].shape == (64, 32) and lw["q_norm"].shape == (16,)
        assert {"ln1", "post_attn_norm", "ln2", "post_mlp_norm"} <= set(lw)
    assert dense["w_gate"].shape == (64, 96) and "w_router" not in dense
    assert expert["w_router"].shape == (64, 16)
    assert expert["router_bias"].shape == (16,)
    assert expert["router_bias"].dtype == jnp.float32
    assert expert["we_gate"].shape == (4, 64, 32)
    assert expert["ws_gate"].shape == (64, 32) and "ws_sigmoid" not in expert
    assert cfg.param_count() == sum(x.size for x in jax.tree.leaves(params))
    assert E.device_counters(cfg) == M.COUNTERS + ("window_rows_read",
                                                   "full_rows_read")


SEQ, N_POS = 128, 40


@functools.lru_cache(maxsize=None)
def _jitted_reference():
    """One program for every comparison of this file: sequences padded at
    the end to 128, positions to 40 (the reference is causal, so padding
    reaches no position)."""
    return jax.jit(lambda params, ids, pos: R.logits_at(
        params, ids, pos, FIELDS, block=SEQ))


def ref_logits(params, seq, pos):
    ids = np.zeros(SEQ, np.int32)
    ids[:len(seq)] = seq
    at = np.zeros(N_POS, np.int32)
    at[:len(pos)] = pos
    return _jitted_reference()(params, jnp.asarray(ids),
                               jnp.asarray(at))[:len(pos)]


def test_cacheless_forward_is_the_reference(model):
    """The whole sequence at once under a band mask, 40 rows against a
    window of 16; float32, summation order apart, on logits of
    std 0.4."""
    fields, cfg, params = model
    ids = jax.random.randint(jax.random.key(1), (2, 40), 1, 256)
    with jax.default_matmul_precision("highest"):
        z = T.forward(params, ids, cfg)
    for b in range(2):
        want = ref_logits(params, np.asarray(ids[b]), np.arange(40))
        np.testing.assert_allclose(z[b], want, atol=3e-4)
        assert float(jnp.std(want)) > 0.3


# ------------------------------------ the engine's programs, on logits

@functools.lru_cache(maxsize=None)
def _programs(kernel: bool, chunk: int):
    """The engine's two cores over both page classes, tapped for logits,
    jitted once a (kernel, chunk): the model rides as an argument."""
    _, cfg, _ = make()

    @jax.jit
    def prefill(params, bufs, tables, ids, pos, plen):
        apos = pos + jnp.arange(chunk, dtype=jnp.int32)[None, :]
        x, bufs, _ = E._paged_forward(params, ids, cfg, bufs, tables, apos,
                                      apos < plen, paged_kernel=kernel)
        return E._all_logits(params, x, cfg), bufs

    @jax.jit
    def decode(params, bufs, tables, toks, lengths, active):
        x, bufs, counts = E._paged_forward(
            params, toks[:, None], cfg, bufs, tables, lengths[:, None],
            active[:, None], paged_kernel=kernel)
        return E._last_logits(params, x, cfg), bufs, counts

    return prefill, decode


def _serve_logits(params, cfg, prompt, n_new, *, kernel, chunk, slots=3,
                  slot=1, ring=None):
    """Chunked prefill and then decode of ONE request through the engine's
    own cores and both page classes, tapped for logits, and the device-side
    counters summed over the decode steps.  ``ring``: entries of the
    request's ring (default: ``kv_pool.ring_pages``)."""
    R_ = ring or ring_pages(cfg, PAGE, chunk)
    P = SEQ // PAGE
    pool = PagedKVPool(cfg, slots * P + 1, PAGE,
                       n_pages_window=slots * R_ + 1)
    full = np.zeros((slots, P), np.int32)
    full[slot] = pool.allocator.alloc(P)
    rings = np.zeros((slots, R_), np.int32)
    rings[slot] = pool.window_allocator.alloc(R_)
    bufs = pool.bufs
    prefill, decode = _programs(kernel, chunk)
    own = (jnp.asarray(full[slot:slot + 1]), jnp.asarray(rings[slot:slot + 1]))
    every = (jnp.asarray(full), jnp.asarray(rings))

    n = len(prompt)
    for pos in range(0, n, chunk):
        ids = np.zeros((1, chunk), np.int32)
        part = prompt[pos:pos + chunk]
        ids[0, :len(part)] = part
        z, bufs = prefill(params, bufs, own, jnp.asarray(ids),
                          jnp.int32(pos), jnp.int32(n))
    out = [z[0, (n - 1) % chunk]]
    active = np.zeros(slots, bool)
    active[slot] = True
    counted = np.zeros(6, np.int64)
    for i in range(n_new - 1):
        toks = np.full(slots, 7, np.int32)      # inactive slots: any token
        toks[slot] = int(jnp.argmax(out[-1]))
        lengths = np.full(slots, 3, np.int32)   # and any length
        lengths[slot] = n + i
        z, bufs, counts = decode(params, bufs, every, jnp.asarray(toks),
                                 jnp.asarray(lengths), jnp.asarray(active))
        out.append(z[slot])
        counted += np.asarray(counts)
    return jnp.stack(out), counted


def _reference_logits(params, prompt, z):
    toks = np.asarray(jnp.argmax(z, axis=-1))
    seq = np.concatenate([prompt, toks[:-1]]).astype(np.int32)
    return ref_logits(params, seq, len(prompt) - 1 + np.arange(len(toks)))


#: float32 everywhere: the engine differs from the reference in summation
#: order alone (online softmax over blocks, the masked expert product),
#: measured 6e-5 on logits of std 1.5.  3e-4 holds the rounding and not a
#: stale or missing row: one wrong key of 16 moves a logit by 1e-2 or more
ATOL = 3e-4


#: every context on the gather path; the kernels, which run interpreted and
#: slowly, one step past the window and six windows out
CASES = [(n, chunk, False) for chunk in (8, 12)
         for n in (8, 15, 16, 17, 40, 96)] \
    + [(n, chunk, True) for chunk in (8, 12) for n in (17, 96)]


@pytest.mark.parametrize(
    "n_prompt,chunk,kernel", CASES,
    ids=[f"{n}-chunk{c}-{'kernels' if k else 'xla'}" for n, c, k in CASES])
def test_engine_prefill_then_decode_is_the_reference_on_logits(
        model, n_prompt, chunk, kernel):
    """Prefill in chunks (8 divides the window of 16, 12 does not) and then
    five decode steps through a ring of window pages and whole-context
    pages (the gather path, or both paged kernels interpreted, each with a
    lower bound), against the reference's whole forward pass of the same
    tokens under its band mask: contexts of half the window, the window
    less one, the window, one more, 2.5 and 6 windows, so that the ring has
    wrapped up to four times when the last token is decoded."""
    fields, cfg, params = model
    prompt = np.random.default_rng(n_prompt).integers(
        1, 256, n_prompt).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        z, counted = _serve_logits(params, cfg, prompt, 6, kernel=kernel,
                                   chunk=chunk)
        want = _reference_logits(params, prompt, z)
    np.testing.assert_allclose(z, want, atol=ATOL)
    # five steps x four expert layers x one live row choosing 3 of 16; the
    # rows one window layer and the full layer read, the new row among them
    a, held, touched, layer_steps, win_rows, full_rows = counted
    assert (a, layer_steps) == (5 * 4 * 3, 5 * 4)
    assert 0 <= touched == held <= a
    lens = n_prompt + 1 + np.arange(5)
    assert full_rows == lens.sum()
    assert win_rows == np.minimum(lens, WINDOW).sum()


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernels"])
def test_a_ring_one_page_short_serves_a_stale_row(model, kernel):
    """``sliding_window + prefill_chunk`` rows is the LEAST ring: with one
    page less the last write of a chunk lands on rows the chunk's first
    query still sees, and the same comparison fails by far more than its
    tolerance (at a context the whole ring serves exactly)."""
    fields, cfg, params = model
    chunk, n_prompt = 8, 40
    prompt = np.random.default_rng(n_prompt).integers(
        1, 256, n_prompt).astype(np.int32)
    need = ring_pages(cfg, PAGE, chunk)
    assert need == (WINDOW + chunk) // PAGE == 6
    with jax.default_matmul_precision("highest"):
        z, _ = _serve_logits(params, cfg, prompt, 6, kernel=kernel,
                             chunk=chunk, ring=need - 1)
        want = _reference_logits(params, prompt, z)
    assert float(jnp.max(jnp.abs(z - want))) > 30 * ATOL


def test_the_ring_view_is_the_window_in_order():
    """Ring of 6 pages of 4 rows (window 16, chunk 8): the view of a decode
    step at position 37 starts at the page of position 22, the first the
    row sees, and lists the ring's pages in position order from there."""
    ring = jnp.asarray([[11, 12, 13, 14, 15, 16], [0] * 6], jnp.int32)
    apos = jnp.asarray([[37], [2]], jnp.int32)
    view, apos_v, lo = ring_view(ring, apos, 16, 4)
    # position p lives at ring[(p // 4) % 6]: pages 5 .. 9 -> entries 5, 0, 1, 2, 3
    assert view[0].tolist() == [16, 11, 12, 13, 14, 15]
    assert (int(apos_v[0, 0]), int(lo[0, 0])) == (37 - 20, 22 - 20)
    # a slot that has not passed the window reads from its first page
    assert (int(apos_v[1, 0]), int(lo[1, 0])) == (2, 0)
    # a chunk of 8 rows at 24 .. 31: its first row sees from 9, page 2
    apos = 24 + jnp.arange(8, dtype=jnp.int32)[None, :]
    view, apos_v, lo = ring_view(ring[:1], apos, 16, 4)
    assert view[0].tolist() == [13, 14, 15, 16, 11, 12]
    assert apos_v[0].tolist() == list(range(16, 24))
    assert lo[0].tolist() == list(range(1, 9))
    with pytest.raises(ValueError, match="whole pages"):
        ring_pages(T.TransformerConfig(**{**FIELDS, "sliding_window": 18}),
                   4, 8)


# ---------------------------------- the kernels' lower bound, on their own

def _plain_attention(q, k, v, lo, hi):
    """Rows q (S, n, hd), row i against keys lo[i] .. hi[i] of k, v
    (T, n_kv, hd): a masked softmax over all T keys, float32."""
    S, n, hd = q.shape
    rep = n // k.shape[1]
    kk, vv = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    t = jnp.arange(k.shape[0])[None, :]
    vis = (t >= jnp.asarray(lo)[:, None]) & (t <= jnp.asarray(hi)[:, None])
    s = jnp.einsum("snd,tnd->snt", q, kk) / np.sqrt(hd)
    p = jax.nn.softmax(jnp.where(vis[:, None, :], s, -jnp.inf), axis=-1)
    return jnp.einsum("snt,tnd->snd", p, vv)


@pytest.fixture(scope="module")
def paged_kv():
    """Three slots' keys and values in shuffled pages of 8 rows (2 KV heads
    of 16), 24 pages a slot: longer than a block of either kernel."""
    n_kv, hd, page, P, B = 2, 16, 8, 24, 3
    ks = jax.random.split(jax.random.key(5), 3)
    k = jax.random.normal(ks[0], (B, P * page, n_kv, hd))
    v = jax.random.normal(ks[1], (B, P * page, n_kv, hd))
    perm = np.random.default_rng(0).permutation(B * P) + 1
    pages = perm.reshape(B, P).astype(np.int32)
    pk = jnp.zeros((B * P + 1, page, n_kv, hd)).at[pages].set(
        k.reshape(B, P, page, n_kv, hd))
    pv = jnp.zeros((B * P + 1, page, n_kv, hd)).at[pages].set(
        v.reshape(B, P, page, n_kv, hd))
    return k, v, pk, pv, jnp.asarray(pages)


def test_the_decode_kernel_reads_from_its_lower_bound(paged_kv):
    """Interpreted, against plain attention over keys ``lo .. apos``: a
    bound inside the first block, one that skips a block of 128
    positions whole, a slot that holds nothing; and with no bound the call
    is the one the other blocks make."""
    k, v, pk, pv, pages = paged_kv
    q = jax.random.normal(jax.random.key(6), (3, 1, 2, 2, 16))
    apos = jnp.asarray([[90], [180], [17]], jnp.int32)
    lo = jnp.asarray([[5], [150], [0]], jnp.int32)
    valid = jnp.asarray([[True], [True], [False]])
    got = paged_attention_decode(q, pk, pv, pages, apos, valid=valid, lo=lo,
                                 interpret=True)
    for b in range(2):
        want = _plain_attention(q[b].reshape(1, 4, 16), k[b], v[b], lo[b],
                                apos[b])
        np.testing.assert_allclose(got[b].reshape(1, 4, 16), want, atol=2e-5)
    assert not np.any(np.asarray(got[2]))
    whole = paged_attention_decode(q, pk, pv, pages, apos, valid=valid,
                                   interpret=True)
    zero = paged_attention_decode(q, pk, pv, pages, apos, valid=valid,
                                  lo=jnp.zeros_like(lo), interpret=True)
    np.testing.assert_allclose(whole, zero, atol=1e-6)
    assert float(jnp.max(jnp.abs(whole[1] - got[1]))) > 1e-3


def test_the_prefill_kernel_slides_its_band_with_the_rows(paged_kv):
    """Interpreted: a chunk of 16 rows at 168 .. 183 under a window of 48
    (row i sees from ``lo + i``: a block of 64 positions is skipped
    whole, and the chunk's later rows see nothing of the first block read),
    its last five rows padding; a chunk at 16 whose bound is below 0."""
    k, v, pk, pv, pages = paged_kv
    S, win = 16, 48
    q = jax.random.normal(jax.random.key(7), (3, S, 2, 2, 16))
    for start, n_valid in ((168, 11), (16, 16)):
        apos = start + jnp.arange(S, dtype=jnp.int32)[None, :] \
            + jnp.zeros((3, 1), jnp.int32)
        valid = jnp.arange(S)[None, :] < jnp.asarray([[n_valid], [S], [0]])
        lo = jnp.maximum(apos - win + 1, 0)
        got = paged_flash_prefill(q, pk, pv, pages, apos, valid=valid, lo=lo,
                                  interpret=True)
        for b, rows in ((0, n_valid), (1, S)):
            want = _plain_attention(q[b].reshape(S, 4, 16), k[b], v[b],
                                    lo[b], apos[b])
            np.testing.assert_allclose(got[b].reshape(S, 4, 16)[:rows],
                                       want[:rows], atol=2e-5)
        assert np.all(np.isfinite(np.asarray(got)))     # padding rows too


# ------------------------------------------------------- the expert layer

def test_the_shares_of_all_ranks_add_up_to_the_uncut_layer():
    """Expert parallelism's cut, tied to the model: the routed parts that
    the program computes as each of 4 ranks (4 of 16 experts a rank; chosen
    by score + bias over ALL experts, weights normalised over the chosen,
    held or not) plus the shared expert counted once are the uncut
    reference layer's output, and each rank's part is its reference
    share's."""
    fields, cfg, _ = make(num_experts=16, router_width=16, expert_offset=0,
                          num_experts_per_tok=4)
    whole = T.init_params(jax.random.key(3), cfg)["layers"][1]
    whole = jax.tree.map(lambda x: 3.0 * x, whole)
    r2 = jax.random.normal(jax.random.key(4), (1, 11, 64))
    with jax.default_matmul_precision("highest"):
        routed_want, shared_want = R.moe(r2[0], whole, fields)
        total = jnp.zeros_like(routed_want)
        for rank in range(4):
            share_fields = {**fields, "num_experts": 4,
                            "expert_offset": 4 * rank}
            share_cfg = T.TransformerConfig(**share_fields,
                                            dtype=jnp.float32, remat=False)
            lw = {**whole, **{k: whole[k][4 * rank:4 * rank + 4]
                              for k in ("we_gate", "we_up", "we_down")}}
            m, counts = M.expert_mlp(r2, lw, cfg=share_cfg)
            routed_ref, shared_ref = R.moe(r2[0], lw, share_fields)
            np.testing.assert_allclose(m[0], routed_ref + shared_ref,
                                       atol=2e-5)
            np.testing.assert_allclose(shared_ref, shared_want, atol=1e-6)
            total = total + (m[0] - shared_ref)
            assert int(counts[0]) == 11 * 4
    np.testing.assert_allclose(total, routed_want, atol=1e-4)
    assert float(jnp.max(jnp.abs(routed_want))) > 1e-2


def test_the_bias_chooses_and_does_not_weigh(model):
    _, cfg, params = model
    lw = params["layers"][1]
    rows = jax.random.normal(jax.random.key(6), (64, 64))
    s = jax.nn.sigmoid(rows @ lw["w_router"])
    w_held, idx = M.route(rows, lw["w_router"], cfg, bias=lw["router_bias"])
    want = jax.lax.top_k(s + lw["router_bias"], 3)[1]
    assert np.array_equal(np.sort(idx, -1), np.sort(want, -1))
    plain = M.route(rows, lw["w_router"], cfg)[1]
    assert not np.array_equal(np.sort(idx, -1), np.sort(plain, -1))
    # the chosen experts' weights are their SCORES' shares of route_scale
    top = jnp.take_along_axis(s, idx, axis=-1)
    w = cfg.routed_scaling_factor * top / top.sum(-1, keepdims=True)
    held = cfg.expert_offset + np.arange(4)
    for t in range(64):
        for j in range(3):
            e = int(idx[t, j])
            if e in held:
                assert float(w_held[t, e - cfg.expert_offset]) \
                    == pytest.approx(float(w[t, j]), rel=1e-5)
    # a layer without the leaf is routed as before: three arguments
    seen = []
    orig = M.route
    try:
        M.route = lambda r2, w_router, cfg: seen.append(1) or orig(
            r2, w_router, cfg)
        no_bias = {k: v for k, v in lw.items() if k != "router_bias"}
        M.expert_mlp(rows[None], no_bias, cfg=cfg)
    finally:
        M.route = orig
    assert seen == [1]


# ------------------------------------------- two page classes, on the host

def _batcher(slots=3, full_pages=40, window_pages=13, ring=4, page=4):
    return ContinuousBatcher(
        slots, PageAllocator(full_pages), page,
        window_allocator=PageAllocator(window_pages), ring_pages=ring)


def _req(rid, n_prompt, n_new):
    return Request(rid=rid, prompt=np.ones(n_prompt, np.int32),
                   max_new_tokens=n_new)


def test_a_request_is_granted_from_both_classes_or_from_neither():
    b = _batcher()
    assert b.pages_needed(_req(0, 5, 3)) == 2
    assert b.pages_needed(_req(0, 5, 3), window=True) == 2   # never wraps
    assert b.pages_needed(_req(0, 50, 10)) == 15
    assert b.pages_needed(_req(0, 50, 10), window=True) == 4  # a ring
    for i, (n, new) in enumerate(((50, 10), (5, 3), (30, 30))):
        b.submit(_req(i, n, new), 0.0)
    got = b.admit(0.0)
    assert [len(r.pages) for r in got] == [15, 2, 15]
    assert [len(r.pages_window) for r in got] == [4, 2, 4]
    assert b.allocator.pages_in_use == 32
    assert b.window_allocator.pages_in_use == 10
    assert not set(got[0].pages_window) & set(got[2].pages_window)


@pytest.mark.parametrize("short", ["window", "full"])
def test_admission_blocks_when_either_class_is_short(short):
    """Head-of-line: the request waits while EITHER class lacks its grant,
    holds nothing of the other class meanwhile, and is seated when a
    retirement returns both."""
    b = _batcher(full_pages=40 if short == "window" else 20,
                 window_pages=7 if short == "window" else 13)
    first, second = _req(0, 50, 10), _req(1, 20, 12)
    b.submit(first, 0.0)
    b.submit(second, 0.0)
    assert b.admit(0.0) == [first] and b.waiting[0] is second
    in_use = (b.allocator.pages_in_use, b.window_allocator.pages_in_use)
    assert in_use == (15, 4)
    assert b.admit(1.0) == []                    # still blocked, nothing held
    assert (b.allocator.pages_in_use,
            b.window_allocator.pages_in_use) == in_use
    assert second.pages is None and second.pages_window is None
    b.retire(first, 2.0)
    assert first.pages is None and first.pages_window is None
    assert (b.allocator.pages_in_use, b.window_allocator.pages_in_use) \
        == (0, 0)
    assert b.admit(3.0) == [second]
    assert (len(second.pages), len(second.pages_window)) == (8, 4)


@pytest.mark.parametrize("how", ["release_all", "double_retire"])
def test_failover_and_a_double_retire_leave_both_allocators_whole(how):
    b = _batcher()
    reqs = [_req(i, n, 4) for i, n in enumerate((50, 5, 30))]
    for r in reqs:
        b.submit(r, 0.0)
    b.admit(0.0)
    if how == "release_all":
        orphans = b.release_all()
        assert orphans == reqs
        assert all(r.pages is None and r.pages_window is None
                   and r.state == "WAITING" for r in reqs)
    else:
        for r in reqs:
            b.retire(r, 1.0)
        with pytest.raises(ValueError, match="double retire"):
            b.retire(reqs[0], 2.0)
    for alloc in (b.allocator, b.window_allocator):
        assert alloc.pages_in_use == 0
        assert sorted(alloc._free) == list(range(1, alloc.n_pages))


def test_the_window_layers_pools_are_rings_and_not_whole_contexts(model):
    """The shapes the engine builds: a window layer's pools hold
    ``max_batch`` rings of ``(window + chunk) / page`` pages and a null
    page, the full layer's ``max_batch`` whole contexts."""
    _, cfg, params = model
    eng = ServingEngine(params, cfg, max_batch=3, page_size=PAGE,
                        max_seq_len=128, prefill_chunk=8)
    assert eng.ring_pages == 6 and eng.n_pages_window == 3 * 6 + 1
    assert eng.n_pages == 3 * 32 + 1
    shapes = [a.shape for a in eng.pool.bufs.k]
    assert shapes == [(19, 4, 2, 16)] * 3 + [(97, 4, 2, 16), (19, 4, 2, 16)]
    assert [a.shape for a in eng.pool.bufs.v] == shapes
    assert eng.pool.window_allocator.n_pages == 19
    assert eng._h_rings.shape == (3, 6) and eng._h_pages.shape == (3, 32)
    with pytest.raises(ValueError, match="n_pages_window"):
        PagedKVPool(cfg, 97, PAGE)
    with pytest.raises(ValueError, match="whole pages"):
        ServingEngine(params, cfg, max_batch=3, page_size=PAGE,
                      max_seq_len=128, prefill_chunk=6)


def test_the_engine_serves_mixed_lengths_and_counts_what_it_read(
        model, tmp_path):
    """Requests under and over the window in one queue, two slots for
    five: tokens are the reference's greedy ones, both classes are whole
    afterwards, and the counters are the round structure's."""
    from distributed_training_sandbox_tpu.telemetry.spans import (
        SpanStream, read_spans)
    fields, cfg, params = model
    stream = SpanStream(str(tmp_path))
    eng = ServingEngine(params, cfg, max_batch=2, page_size=PAGE,
                        max_seq_len=128, prefill_chunk=8, sync_every=3,
                        telem=type("Telem", (), {
                            "spans": stream, "metrics": None,
                            "step": lambda self, **kw: None,
                            "attach_step_hlo": lambda self, *a, **kw: None})())
    rng = np.random.default_rng(0)
    shapes = ((8, 4), (17, 20), (40, 9), (100, 20), (33, 1))
    reqs = [eng.submit(rng.integers(1, 256, n), max_new_tokens=m)
            for n, m in shapes]
    eng.run()
    stream.close()
    for r in reqs:
        seq = np.concatenate([r.prompt, np.asarray(r.tokens[:-1], np.int32)])
        z = ref_logits(params, seq,
                       r.n_prompt - 1 + np.arange(len(r.tokens)))
        assert r.tokens == np.argmax(np.asarray(z), -1).tolist(), r.rid
    s = eng.stats
    assert eng.pool.allocator.pages_in_use == 0
    assert eng.pool.window_allocator.pages_in_use == 0
    assert not eng._h_rings.any() and not eng._h_pages.any()
    # a decode step reads len rows in the full layer, min(len, 16) in a
    # window layer: summed over the steps each request was live for
    lens = np.concatenate([n + 1 + np.arange(m - 1) for n, m in shapes])
    assert s["full_rows_read"] == lens.sum()
    assert s["window_rows_read"] == np.minimum(lens, WINDOW).sum()
    # a prompt's row t sees min(t + 1, 16) keys in one window layer
    assert s["window_pairs_prefilled"] == sum(
        np.minimum(np.arange(n) + 1, WINDOW).sum() for n, _ in shapes)
    assert s["moe_expert_layer_steps"] == 4 * s["decode_steps"]
    # the most granted at once: two requests' grants, by class
    grants = sorted(((-(-(n + m) // PAGE)), min(-(-(n + m) // PAGE), 6))
                    for n, m in shapes)
    assert grants[-1][0] <= s["full_pages_peak"] \
        <= grants[-1][0] + grants[-2][0]
    assert 6 < s["window_pages_peak"] <= 12
    util = eng.slo_report()["scheduler"]["peak_pool_util"]
    assert util == {"full": round(s["full_pages_peak"] / 64, 4),
                    "window": round(s["window_pages_peak"] / 12, 4)}
    assert eng.slo_report()["pool"]["n_pages_window"] == 13
    # serve/admit carries the round's two grants
    admits = [e for e in read_spans(str(tmp_path))
              if e["name"] == "serve/admit"]
    assert len(admits) == s["rounds"]
    got = lambda key: sum(  # noqa: E731
        (e.get("args") or e).get(key, 0) for e in admits)
    assert got("pages_full") == sum(g[0] for g in grants)
    assert got("pages_window") == sum(g[1] for g in grants)


# ------------------------------------------------------- refused by name

@pytest.mark.parametrize("kw,what", [
    ({"kv_quant": True}, "kv_quant"),
    ({"spec_k": 2, "draft_layers": 1}, "spec_k"),
    ({"flash_prefill": True}, "flash_prefill"),
    ({"disaggregate": True}, "disaggregate"),
    ({"prefix_cache": True}, "prefix_cache"),
    ({"mesh": "a mesh"}, "a tp mesh"),
    ({"hbm_budget_gb": 8.0}, "hbm_budget_gb"),
])
def test_the_engine_refuses_what_is_not_built_for_the_block(model, kw, what):
    _, cfg, params = model
    with pytest.raises(NotImplementedError,
                       match=f"sliding-window \\+ full attention block.*"
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
                       match="sliding-window \\+ full attention block.*"
                             "not built"):
        call()


@pytest.mark.parametrize("over,match", [
    ({"moe_intermediate_size": 0}, r"needs \['moe_intermediate_size'\]"),
    ({"global_attn_every_n_layers": 0},
     r"needs \['global_attn_every_n_layers'\]"),
    ({"num_shared_experts": 0}, r"needs \['num_shared_experts'\]"),
    ({"num_dense_layers": 9}, "num_dense_layers must lie"),
    ({"expert_offset": 14}, "not among the router's 16"),
    ({"sandwich_norm": False}, "sandwich_norm=True only"),
    ({"nope_interval": 4}, "nope_interval=0 only"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings=False only"),
    ({"kv_lora_rank": 8}, "."),
    ({"attention_impl": "flash"}, "attention_impl='xla' only"),
])
def test_a_variant_the_block_does_not_build_is_refused_by_name(over, match):
    with pytest.raises(ValueError, match=match):
        T.TransformerConfig(**{**FIELDS, **over})


# --------------------------------------- lowered for a TPU, published widths

def test_both_programs_lower_for_tpu_at_published_widths(monkeypatch):
    """The engine's decode and prefill programs at the cell's widths and
    page classes (one dense and one period of layers; fewer slots than the
    cell, which changes no kernel), lowered FOR a TPU on this host: every
    layer's attention is one Mosaic call over its own pool, the window
    layers' with a third (decode) or fourth (prefill) scalar-prefetched
    operand, nothing gathers a view, and a window layer's pool is
    ``slots x 288 + 1`` pages against the full layer's ``slots x 1088 +
    1``."""
    import json
    from benchmarks import harness
    f = json.loads((ROOT / "benchmarks/configs/"
                    "trinity-large-ep32-l5-serve.json").read_text())
    cfg = harness.model_config(f["fields"])
    B, page, chunk, seq = 4, 16, 512, 17_408
    P, R_ = seq // page, ring_pages(cfg, page, chunk)
    assert (P, R_) == (1088, 288)
    sd = jax.ShapeDtypeStruct
    i32 = lambda *shape: sd(shape, jnp.int32)  # noqa: E731
    params = jax.eval_shape(lambda: T.init_params(jax.random.key(0), cfg))
    bufs = jax.eval_shape(lambda: PagedKVPool(
        cfg, B * P + 1, page, n_pages_window=B * R_ + 1).bufs)
    assert [a.shape[0] for a in bufs.k] == [B * R_ + 1] * 3 \
        + [B * P + 1, B * R_ + 1]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    lower = lambda step, args: step.trace(*args).lower(  # noqa: E731
        lowering_platforms=("tpu",)).as_text()
    text = lower(E.make_serve_decode_step(cfg, paged_kernel=True), (
        bufs, params, (i32(B, P), i32(B, R_)), i32(B), i32(B), i32(B),
        sd((B,), jnp.bool_), i32(6 + 8 * B)))
    assert text.count("call @_decode_float") == 5
    assert f"tensor<{B}x{P * page}x8x128" not in text     # no gathered view
    assert f"tensor<{B}x{R_ * page}x8x128" not in text
    text = lower(E.make_serve_prefill_step(cfg, paged_kernel=True), (
        bufs, params, (i32(1, P), i32(1, R_)), i32(1, chunk), i32(), i32()))
    assert text.count("call @_prefill_float") == 5
    assert f"tensor<1x{P * page}x8x128" not in text
