"""The gated delta-rule hybrid with expert layers (``models/gdn_moe.py``) on
the serving path, at a tiny size, float32, seeded weights, on the CPU: the
three forms of the GROUPED-head recurrence against each other and against
the reference's token scan, the step kernel (interpreted) against the XLA
form, the cache-less forward and the engine's own programs against the
benchmark's plain reference on logits, the sixteen shares of an expert
layer against the uncut layer, state slots and counters, the pool stored
as the kernels' slab, what is refused by name, and both engine programs
lowered for a TPU at the published widths."""

import contextlib
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmarks.reference import gdn_moe as R  # noqa: E402
from distributed_training_sandbox_tpu.models import gdn_hybrid as G  # noqa: E402
from distributed_training_sandbox_tpu.models import gdn_moe as N  # noqa: E402
from distributed_training_sandbox_tpu.models import mla_moe as M  # noqa: E402
from distributed_training_sandbox_tpu.models import transformer as T  # noqa: E402
from distributed_training_sandbox_tpu.ops.gdn_step import (  # noqa: E402
    gdn_decode_step, head_group, step_kernel_takes)
from distributed_training_sandbox_tpu.serving import ServingEngine  # noqa: E402
from tests.serving_blocks import FIELDS as BLOCK_FIELDS  # noqa: E402
from distributed_training_sandbox_tpu.serving import engine as E  # noqa: E402
from distributed_training_sandbox_tpu.serving.kv_pool import (  # noqa: E402
    PagedKVPool, paged_layers, pool_shape, row_layout, slab_pool,
    slot_state_bytes, token_row_bytes)
from tests.gdn_scan_cases import (  # noqa: E402
    CASES, assert_as_exact_as_the_solve, recurrence64, scan_case)

FIELDS = BLOCK_FIELDS["gdn_moe"]


def make(seed=0, scale=2.0, **over):
    """Seeded weights, scaled as the benchmark scales them, and the
    zero-centred norm weights moved off their init of 0, so that ``1 + w``
    is exercised."""
    fields = {**FIELDS, **over}
    cfg = T.TransformerConfig(**fields, dtype=jnp.float32, remat=False)
    params = jax.tree.map(lambda x: x * scale,
                          T.init_params(jax.random.key(seed), cfg))
    key = jax.random.key(seed + 100)

    def off_zero(path, x):
        name = str(path[-1])
        if "norm" in name and "o_norm" not in name:
            k = jax.random.fold_in(key, sum(map(ord, str(path))))
            return 0.3 * jax.random.normal(k, x.shape, x.dtype)
        return x

    return fields, cfg, jax.tree_util.tree_map_with_path(off_zero, params)


@pytest.fixture(scope="module")
def model():
    return make()


@pytest.fixture(autouse=True)
def small_sub_chunks(monkeypatch):
    """Sub-chunks of 4 rows, so that a 16-row prefill chunk scans four of
    them in sequence and a prompt ends inside one."""
    monkeypatch.setattr(G, "SCAN_CHUNK", 4)


def test_the_block_is_selected_and_counted(model):
    _, cfg, params = model
    assert cfg.gdn_moe and cfg.gdn_hybrid and not cfg.mla_moe
    assert cfg.block_module is N and cfg.held_experts == 4
    assert N.layer_kinds(cfg) == ("linear",) * 3 + ("full",)
    lin, full = params["layers"][0], params["layers"][3]
    assert lin["w_q"].shape == (64, 16) and lin["w_v"].shape == (64, 64)
    assert lin["w_b"].shape == (64, 4) and lin["conv_w"].shape == (4, 96)
    assert full["wq"].shape == (64, 4 * 2 * 16)         # query | gate a head
    assert full["q_norm"].shape == (16,) == full["k_norm"].shape
    for lw in (lin, full):
        assert lw["w_router"].shape == (64, 16)
        assert lw["we_gate"].shape == (4, 64, 32)
        assert lw["ws_sigmoid"].shape == (64, 1)
        assert {"input_norm", "post_attn_norm"} <= set(lw)
        assert "post_mlp_norm" not in lw and "w_gate" not in lw
    assert cfg.param_count() == sum(x.size for x in jax.tree.leaves(params))
    assert G.state_shape(cfg) == (4, 8, 16) and G.tail_shape(cfg) == (3, 96)
    assert N.rotary_dim(cfg) == 4
    init = T.init_params(jax.random.key(0), cfg)
    assert not np.any(np.asarray(init["final_norm"]))   # zero-centred: 0
    assert np.all(np.asarray(init["layers"][0]["o_norm"]) == 1)   # plain


# ------------------------------ the three forms, at grouped value heads

def _inputs(seed, B=2, S=13, nk=2, n=4, dk=8, dv=16, beta_max=1.0):
    ks = jax.random.split(jax.random.key(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (B, S, nk, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (B, S, nk, dk)))
    v = jax.random.normal(ks[2], (B, S, n, dv))
    g = -jax.random.uniform(ks[3], (B, S, n), minval=0.0, maxval=2.0)
    beta = beta_max * jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, n)))
    s0 = jax.random.normal(ks[5], (B, n, dk, dv))       # NON-zero state
    return q, k, v, g, beta, s0


def _token_by_token(q, k, v, g, beta, s, step=G.recurrent_step):
    out, s = [], G.pack_state(s)
    for t in range(q.shape[1]):
        o, s = step(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], s)
        out.append(o)
    return jnp.stack(out, axis=1), G.unpack_state(s, v.shape[2])


@pytest.mark.parametrize("zero_state", [False, True], ids=["carried", "zero"])
@pytest.mark.parametrize("rows", [13, 16, 1, 5])
def test_the_three_forms_agree_at_grouped_heads(rows, zero_state):
    """Token step, chunked scan (sub-chunks of 4) and the reference's scan,
    from a carried state and from zeros, two value heads a key head."""
    q, k, v, g, beta, s0 = _inputs(1, S=rows)
    if zero_state:
        s0 = jnp.zeros_like(s0)
    o_want, s_want = recurrence64(q, k, v, g, beta, s0)
    with jax.default_matmul_precision("highest"):
        o, s = G.chunked_scan(q, k, v, g, beta, s0)
        o_t, s_t = _token_by_token(q, k, v, g, beta, s0)
    np.testing.assert_allclose(o_t, o_want, atol=2e-5)
    np.testing.assert_allclose(s_t, s_want, atol=2e-5)
    np.testing.assert_allclose(o, o_want, atol=3e-5)
    np.testing.assert_allclose(s, s_want, atol=3e-5)


def test_a_wrong_key_head_is_another_recurrence():
    """The grouping is visible to the test: value heads paired with the
    other key head give other outputs."""
    q, k, v, g, beta, s0 = _inputs(2)
    o_want, _ = recurrence64(q, k, v, g, beta, s0)
    o, _ = _token_by_token(q[:, :, ::-1], k[:, :, ::-1], v, g, beta, s0)
    assert float(np.max(np.abs(np.asarray(o) - o_want))) > 1e-2


@pytest.mark.parametrize("dims", [(2, 4, 8, 16), (2, 2, 8, 16), (1, 4, 8, 16),
                                  (4, 8, 16, 128), (3, 6, 8, 64)],
                         ids=lambda d: "x".join(map(str, d)))
def test_the_step_kernel_is_the_xla_form_at_grouped_heads(dims):
    """``ops/gdn_step.py`` interpreted against ``recurrent_step``'s XLA
    form: key heads serving 1, 2 or 4 value heads, a head group of one
    tile pair (8 heads of 128), inactive slots (g = beta = 0) bit-unchanged
    and given o = 0."""
    nk, n, dk, dv = dims
    q, k, v, g, beta, s0 = _inputs(3, B=5, S=1, nk=nk, n=n, dk=dk, dv=dv)
    q, k, v, g, beta = (a[:, 0] for a in (q, k, v, g, beta))
    dead = jnp.array([False, True, False, False, True])
    g = jnp.where(dead[:, None], 0.0, g)
    beta = jnp.where(dead[:, None], 0.0, beta)
    s0 = G.pack_state(s0)
    o_want, s_want = G.recurrent_step(q, k, v, g, beta, s0)
    o, s = gdn_decode_step(q, k, v, g, beta, s0, interpret=True)
    live = ~np.asarray(dead)
    np.testing.assert_allclose(np.asarray(o)[live], np.asarray(o_want)[live],
                               atol=1e-5)
    np.testing.assert_allclose(s, s_want, atol=1e-5)
    assert np.array_equal(np.asarray(s)[np.asarray(dead)],
                          np.asarray(s0)[np.asarray(dead)])    # bitwise
    assert not np.any(np.asarray(o)[np.asarray(dead)])


def test_the_head_group_is_whole_lane_tiles_and_more_than_one():
    assert head_group(30, 192) == 2        # 384 lanes: the older block's
    assert head_group(32, 128) == 2        # never a single tile (Mosaic)
    assert head_group(32, 64) == 4 and head_group(32, 256) == 1
    assert step_kernel_takes(32, 128, 128) and step_kernel_takes(30, 96, 192)
    assert not step_kernel_takes(30, 96, 100)      # a head ends inside a tile


def test_padding_rows_and_inactive_slots_change_no_state_bit():
    q, k, v, g, beta, s0 = _inputs(4, S=16)
    keep = (jnp.arange(16) < 9)[None, :, None]
    g, beta = jnp.where(keep, g, 0.0), jnp.where(keep, beta, 0.0)
    with jax.default_matmul_precision("highest"):
        _, s = G.chunked_scan(q, k, v, g, beta, s0)
        _, s9 = G.chunked_scan(q[:, :9], k[:, :9], v[:, :9], g[:, :9],
                               beta[:, :9], s0)
    np.testing.assert_allclose(s, s9, atol=1e-6)
    for step in (G.recurrent_step,
                 lambda *a: gdn_decode_step(*a, interpret=True)):
        _, s1 = step(q[:, 12], k[:, 12], v[:, 12], g[:, 12], beta[:, 12],
                     G.pack_state(s0))
        assert np.array_equal(np.asarray(s1), np.asarray(G.pack_state(s0)))


@pytest.mark.parametrize("rows", [64, 150])
def test_the_scan_at_the_published_sub_chunk_is_the_token_recurrence(
        monkeypatch, rows):
    """Sub-chunks of 64 rows, the size the cells run, one whole and two
    and a part, from a carried state."""
    monkeypatch.setattr(G, "SCAN_CHUNK", 64)
    q, k, v, g, beta, s0 = _inputs(5, B=1, S=rows)
    o_want, s_want = recurrence64(q, k, v, g, beta, s0)
    with jax.default_matmul_precision("highest"):
        o, s = G.chunked_scan(q, k, v, g, beta, s0)
    np.testing.assert_allclose(o, o_want, atol=1e-4)
    np.testing.assert_allclose(s, s_want, atol=1e-4)


@pytest.mark.parametrize("case", CASES)
def test_the_grouped_scan_is_as_exact_as_the_solve_on_repeated_keys(case):
    """``tests/test_gdn_hybrid.py``'s test on repeated keys at this
    block's heads and gate: two value heads a key head (the repeated keys
    reach BOTH heads' systems, whose decay and beta are their own), beta at
    its bound of 1, 64-row sub-chunks, against the recurrence in float64.
    Measured: 2.5e-7 to 4.1e-7 on ``o``, 1.6e-7 to 2.4e-7 on the state,
    the solve's own readings to 20% (at beta 1 a repeated key has ``1 -
    beta k.k = 0``, and the system is tame)."""
    assert_as_exact_as_the_solve(
        G, scan_case(case, nk=2, n=4, beta_max=1.0))


# ------------------------------------------------- against the reference

def test_cacheless_forward_is_the_reference(model):
    fields, cfg, params = model
    ids = jax.random.randint(jax.random.key(1), (2, 37), 1, 256)
    with jax.default_matmul_precision("highest"):
        z = T.forward(params, ids, cfg)
    for b in range(2):
        want = R.logits_at(params, ids[b], jnp.arange(37), fields, block=37)
        np.testing.assert_allclose(z[b], want, atol=2e-4)


def _pool(cfg, slots, page=8, seq=64):
    P = seq // page
    return PagedKVPool(cfg, slots * P + 1, page, n_slots=slots), P


def _serve_logits(params, cfg, prompt, n_new, *, kernel, chunk=16, slots=3,
                  slot=1, bufs=None):
    """Chunked prefill and then decode of ONE request through the engine's
    own cores, tapped for logits; the pool's buffers afterwards; and the
    device-side counters summed over the decode steps."""
    pool, P = _pool(cfg, slots)
    pages = np.zeros((slots, P), np.int32)
    pages[slot] = pool.allocator.alloc(P)
    bufs = pool.bufs if bufs is None else bufs

    @jax.jit
    def prefill(bufs, ids, pos, plen):
        apos = pos + jnp.arange(chunk, dtype=jnp.int32)[None, :]
        x, bufs, _ = E._paged_forward(
            params, ids, cfg, bufs, jnp.asarray(pages[slot:slot + 1]), apos,
            apos < plen, paged_kernel=kernel, slot=jnp.int32(slot))
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
    counted = np.zeros(5, np.int64)
    for i in range(n_new - 1):
        toks = np.full(slots, 7, np.int32)      # inactive slots: any token
        toks[slot] = int(jnp.argmax(out[-1]))
        lengths = np.zeros(slots, np.int32)
        lengths[slot] = n + i
        z, bufs, counts = decode(bufs, jnp.asarray(toks),
                                 jnp.asarray(lengths), jnp.asarray(active))
        out.append(z[slot])
        counted += np.asarray(counts)
    return jnp.stack(out), bufs, counted


def _reference_logits(params, fields, prompt, z):
    toks = np.asarray(jnp.argmax(z, axis=-1))
    seq = np.concatenate([prompt, toks[:-1]]).astype(np.int32)
    pos = len(prompt) - 1 + np.arange(len(toks))
    return R.logits_at(params, jnp.asarray(seq), jnp.asarray(pos), fields,
                       block=len(seq))


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernels"])
@pytest.mark.parametrize("n_prompt", [37, 33, 16, 5])
def test_engine_prefill_then_decode_is_the_reference_on_logits(
        model, kernel, n_prompt):
    """Prefill in chunks of 16 carrying state and conv tail, then six
    decode steps through state slots and pages (gather path, or all three
    kernels interpreted), against the reference's whole forward pass of
    the same tokens.  float32 everywhere: the paths differ from the
    reference in summation order (chunked scan, online softmax), measured
    4e-5 on logits of std 0.3; 3e-4 catches a lost tail, a stale state, a
    wrong page, mask, gate or rotary dim and not the rounding."""
    fields, cfg, params = model
    prompt = np.random.default_rng(n_prompt).integers(
        1, 256, n_prompt).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        z, _, counted = _serve_logits(params, cfg, prompt, 7, kernel=kernel)
    np.testing.assert_allclose(z, _reference_logits(params, fields, prompt,
                                                    z), atol=3e-4)
    # six steps x four expert layers x one live row choosing 3 of 16
    a, held, touched, layer_steps, live = counted
    assert (a, layer_steps, live) == (6 * 4 * 3, 6 * 4, 6)
    assert 0 <= touched == held <= a


def test_an_inactive_slot_and_a_first_chunk_keep_to_their_own_state(model):
    """Garbage in every slot: the request's first chunk starts from zeros
    whatever its slot held, and the OTHER slots' state, tail and rows are
    bit-unchanged by its prefill and its decode steps."""
    fields, cfg, params = model
    prompt = np.random.default_rng(5).integers(1, 256, 21).astype(np.int32)
    pool, _ = _pool(cfg, 3)
    junk = jax.tree.map(
        lambda a: jax.random.normal(jax.random.key(7), a.shape, a.dtype),
        (pool.bufs.state, pool.bufs.conv))
    dirty = pool.bufs._replace(state=junk[0], conv=junk[1])
    for kernel in (False, True):
        with jax.default_matmul_precision("highest"):
            z0, _, _ = _serve_logits(params, cfg, prompt, 4, kernel=kernel)
            z1, bufs, _ = _serve_logits(params, cfg, prompt, 4,
                                        kernel=kernel, bufs=dirty)
        assert np.array_equal(np.asarray(z0), np.asarray(z1))
        for got, was in zip(bufs.state + bufs.conv, junk[0] + junk[1]):
            assert np.array_equal(np.asarray(got)[[0, 2]],
                                  np.asarray(was)[[0, 2]])
            assert not np.array_equal(np.asarray(got)[1], np.asarray(was)[1])


@pytest.mark.parametrize("fault", [
    "state_in_bf16", "beta_with_the_factor_2",
    "value_heads_on_the_wrong_key_head", "attention_gate_left_out",
    "rotary_over_the_whole_head", "renormalise_over_held",
    "shared_gate_left_out", "int8"])
def test_a_planted_fault_leaves_the_reference_on_logits(model, fault):
    """Each fault of ``tests/benchmark/gdn_moe_faults.py`` (and int8
    projections, the fault that needs no code) moves the logits of
    prefill-in-chunks-then-decode off the reference by more than the 3e-4
    the sound program is held to; all are decode-step faults, so they show
    from the second served token on and the first is sound."""
    from tests.benchmark import gdn_moe_faults
    fields, cfg, params = model
    plant = contextlib.nullcontext
    if fault == "int8":
        cfg = T.TransformerConfig(**{**fields, "matmul_precision": "int8"},
                                  dtype=jnp.float32, remat=False)
    else:
        plant = gdn_moe_faults.FAULTS[fault][0]
    prompt = np.random.default_rng(37).integers(1, 256, 37).astype(np.int32)
    with plant(), jax.default_matmul_precision("highest"):
        z, _, _ = _serve_logits(params, cfg, prompt, 7, kernel=False)
    want = _reference_logits(params, fields, prompt, z)
    gap = np.max(np.abs(np.asarray(z) - np.asarray(want)), axis=-1)
    assert gap[1:].max() > 1e-3, gap
    if fault != "int8":                 # int8 projections touch prefill too
        assert gap[0] < 3e-4, gap


# -------------------------------------------------------- the share test

def test_sixteen_shares_add_up_to_the_uncut_layer():
    """Expert parallelism's cut, tied to the model: the routed parts that
    the program computes as each of the 16 ranks (2 of 32 experts a rank;
    weights normalised over the CHOSEN experts, held or not) plus the
    shared expert counted once are the uncut reference layer's output, and
    each rank's part is its reference share's."""
    fields, cfg, _ = make(num_experts=32, router_width=32, expert_offset=0,
                          num_experts_per_tok=5)
    whole = T.init_params(jax.random.key(3), cfg)["layers"][0]
    whole = jax.tree.map(lambda x: 3.0 * x, whole)
    r2 = jax.random.normal(jax.random.key(4), (1, 11, 64))
    with jax.default_matmul_precision("highest"):
        routed_want, shared_want = R.moe(r2[0], whole, fields)
        total = jnp.zeros_like(routed_want)
        for rank in range(16):
            share_fields = {**fields, "num_experts": 2,
                            "expert_offset": 2 * rank}
            share_cfg = T.TransformerConfig(**share_fields,
                                            dtype=jnp.float32, remat=False)
            lw = {**whole, **{k: whole[k][2 * rank:2 * rank + 2]
                              for k in ("we_gate", "we_up", "we_down")}}
            m, counts = M.expert_mlp(r2, lw, cfg=share_cfg)
            routed_ref, shared_ref = R.moe(r2[0], lw, share_fields)
            np.testing.assert_allclose(m[0], routed_ref + shared_ref,
                                       atol=2e-5)
            np.testing.assert_allclose(shared_ref, shared_want, atol=1e-6)
            total = total + (m[0] - shared_ref)
            assert int(counts[0]) == 11 * 5
    np.testing.assert_allclose(total, routed_want, atol=1e-4)
    assert float(jnp.max(jnp.abs(routed_want))) > 1e-2


def test_the_router_scores_with_a_softmax_over_the_whole_width(model):
    _, cfg, params = model
    lw = params["layers"][0]
    rows = jax.random.normal(jax.random.key(6), (9, 64))
    w_held, idx = M.route(rows, lw["w_router"], cfg)
    p = jax.nn.softmax(rows @ lw["w_router"], axis=-1)
    top = jnp.sort(p, axis=-1)[:, -3:]
    assert idx.shape == (9, 3) and w_held.shape == (9, 4)
    chosen = (idx[:, :, None] == jnp.arange(4, 8)).any(1)   # held: 4..7
    want = jnp.where(chosen, p[:, 4:8] / top.sum(-1, keepdims=True), 0.0)
    np.testing.assert_allclose(w_held, want, atol=1e-6)
    assert N.ROUTER_SCORING == "softmax" and M.ROUTER_SCORING == "sigmoid"


# ------------------------------------------------- pool, engine, counters

def test_the_pool_is_the_kernels_slab_where_a_head_is_wider_than_a_tile():
    """At the published head dim of 256 with 2 KV heads the pool is STORED
    (n_pages, page x heads, hd); at one lane tile or narrower, 4-D as the
    other blocks'.  The account does not change."""
    wide = T.TransformerConfig(**{**FIELDS, "head_dim": 256,
                                  "hidden_size": 64}, dtype=jnp.bfloat16)
    assert slab_pool(wide) and row_layout(wide) == ((2, 256), True)
    assert pool_shape(wide, 9, 16) == (9, 32, 256)
    pool = PagedKVPool(wide, 9, 16, n_slots=2)
    assert pool.bufs.k[0].shape == pool.bufs.v[0].shape == (9, 32, 256)
    assert token_row_bytes(wide) == 2 * 2 * 256 * 2 == pool.row_bytes
    tiny = T.TransformerConfig(**FIELDS, dtype=jnp.float32)
    assert not slab_pool(tiny) and pool_shape(tiny, 9, 8) == (9, 8, 2, 16)
    assert paged_layers(tiny) == 1
    assert slot_state_bytes(tiny) == 3 * (4 * 8 * 16 * 4 + 3 * 96 * 4)
    assert not slab_pool(T.TINY_LM)


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernels"])
def test_a_slab_pool_serves_what_the_reference_computes(kernel):
    """Heads of 256 (two lane tiles; float32 here) through a pool stored as
    the slab: scatter by row ``offset * heads + head``, the kernels'
    view of it, the flash prefill kernel's staging a lane tile a plane."""
    fields, cfg, params = make(seed=2, head_dim=256, partial_rotary_factor=0.25)
    assert slab_pool(cfg) and N.rotary_dim(cfg) == 64
    prompt = np.random.default_rng(3).integers(1, 256, 19).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        z, bufs, _ = _serve_logits(params, cfg, prompt, 4, kernel=kernel)
    assert bufs.k[0].ndim == 3
    np.testing.assert_allclose(z, _reference_logits(params, fields, prompt,
                                                    z), atol=3e-4)


def test_the_engine_counts_both_blocks_counters(model):
    _, cfg, params = model
    eng = ServingEngine(params, cfg, max_batch=3, page_size=8, max_seq_len=64,
                        prefill_chunk=16)
    for i in range(5):
        eng.submit(np.arange(1, 20 + i), 6)
    done = eng.run()
    s = eng.stats
    assert len(done) == 5 and all(len(r.tokens) == 6 for r in done)
    assert s["state_resets"] == s["admitted"] == 5
    assert s["lin_scan_rows"] == sum(range(19, 24))
    assert s["moe_expert_layer_steps"] == 4 * s["decode_steps"]
    assert s["moe_assignments"] == 3 * 4 * s["state_slot_steps"] > 0
    assert 0 < s["moe_experts_touched"] <= s["moe_assignments_held"]
    assert s["lin_step_inplace_steps"] == s["decode_inplace_steps"] == 0
    assert eng.retraces_after_warmup() == 0


# ------------------------------------------------------------ the refusals

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
    with pytest.raises(NotImplementedError,
                       match=f"hybrid block with expert layers.*"
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
                       match="hybrid block with expert layers.*not built"):
        call()


@pytest.mark.parametrize("over,match", [
    ({"moe_intermediate_size": 0}, r"needs \['moe_intermediate_size'\]"),
    ({"shared_expert_intermediate_size": 0},
     r"needs \['shared_expert_intermediate_size'\]"),
    ({"router_width": 6}, "held experts 4..7 are not among the router's 6"),
    ({"num_experts_per_tok": 17}, "num_experts_per_tok exceeds router_width"),
    ({"partial_rotary_factor": 0.2}, "even number of rotary dims"),
    ({"partial_rotary_factor": 1.5}, "even number of rotary dims"),
    ({"norm_topk_prob": False}, "norm_topk_prob=True only"),
    ({"n_routed_experts": 4}, "n_routed_experts=0 only"),
    ({"n_shared_experts": 1}, "n_shared_experts=0 only"),
    ({"routed_scaling_factor": 2.5}, "routed_scaling_factor=1.0 only"),
    ({"sandwich_norm": True}, "sandwich_norm=False only"),
    ({"linear_num_value_heads": 3}, "a multiple of linear_num_key_heads"),
    ({"n_experts": 4}, "n_experts=0 only"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings=False only"),
    ({"full_attention_interval": 0}, r"needs \['full_attention_interval'\]"),
])
def test_a_variant_the_block_does_not_build_is_refused_by_name(over, match):
    with pytest.raises(ValueError, match=match):
        T.TransformerConfig(**{**FIELDS, **over})


# ------------------------------------- for a TPU, at the published widths

def _published(monkeypatch, B=64, page=16, P=256, layers=4):
    monkeypatch.setattr(G, "SCAN_CHUNK", 64)
    cfg = T.TransformerConfig(
        vocab_size=151936, hidden_size=2048, intermediate_size=5120,
        num_hidden_layers=layers, num_attention_heads=16,
        num_key_value_heads=2, head_dim=256, rms_norm_eps=1e-6,
        rope_theta=1e7, tie_word_embeddings=False, nope_interval=0,
        full_attention_interval=4, linear_num_key_heads=16,
        linear_num_value_heads=32, linear_key_head_dim=128,
        linear_value_head_dim=128, linear_conv_kernel_dim=4, num_experts=32,
        router_width=512, expert_offset=0, num_experts_per_tok=10,
        moe_intermediate_size=512, shared_expert_intermediate_size=512,
        norm_topk_prob=True, partial_rotary_factor=0.25, dtype=jnp.bfloat16,
        remat=False)
    sd = jax.ShapeDtypeStruct
    params = jax.eval_shape(lambda: T.init_params(jax.random.key(0), cfg))
    bufs = jax.eval_shape(
        lambda: PagedKVPool(cfg, B * P + 1, page, n_slots=B).bufs)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    decode_args = (bufs, params, sd((B, P), jnp.int32), sd((B,), jnp.int32),
                   sd((B,), jnp.int32), sd((B,), jnp.int32),
                   sd((B,), jnp.bool_), sd((5,), jnp.int32))
    return cfg, params, bufs, decode_args


def test_both_programs_lower_for_tpu_at_published_widths(monkeypatch):
    """The engine's decode and prefill programs at the cell's shapes (one
    period of four layers, a 256-row chunk), lowered FOR a TPU on this
    host: the full-attention layer is one Mosaic call over the pool as it
    is stored (no reshape of a 4-D pool to the slab), nothing gathers the
    view; every linear layer's decode step is one Mosaic call on the state
    as stored, in place, with 16 key heads' columns for 32 value heads."""
    from distributed_training_sandbox_tpu.ops.flash_prefill import (
        prefill_kernel_takes)
    from distributed_training_sandbox_tpu.ops.paged_attention import (
        decode_kernel_takes)
    B, page, P, chunk = 64, 16, 256, 256
    cfg, params, bufs, decode_args = _published(monkeypatch, B, page, P)
    assert decode_kernel_takes(cfg.dtype, 256, 16)
    assert prefill_kernel_takes(cfg.dtype, 256, 16, chunk)
    assert step_kernel_takes(*G.state_shape(cfg))
    assert G.slot_state_bytes(cfg) == 2_097_152 + 3 * 8_192 * 2
    assert bufs.state[0].shape == (64, 128, 4096) and len(bufs.k) == 1
    assert bufs.k[0].shape == (B * P + 1, 32, 256)
    sd = jax.ShapeDtypeStruct
    lower = lambda step, args: step.trace(*args).lower(  # noqa: E731
        lowering_platforms=("tpu",)).as_text()
    text = lower(E.make_serve_decode_step(cfg, paged_kernel=True), decode_args)
    assert "_decode_float" in text
    assert f"tensor<{B}x{P * page}x2x256" not in text      # no gathered view
    assert f"tensor<{B * P + 1}x16x2x256xbf16>" in text   # the kernels' view
    assert text.count("call @_step(") == 3 \
        == N.layer_kinds(cfg).count("linear")
    assert text.count('kernel_name = "_step_kernel"') == 1
    assert "tensor<64x128x32xf32>" in text                 # k | q: 2 x 16 columns
    assert "64x128x32x128" not in text                     # no XLA form
    text = lower(
        E.make_serve_prefill_step(cfg, paged_kernel=True),
        (bufs, params, sd((1, P), jnp.int32), sd((1, chunk), jnp.int32),
         sd((), jnp.int32), sd((), jnp.int32), sd((), jnp.int32)))
    assert "_prefill_float" in text
    assert f"tensor<1x{P * page}x2x256" not in text
    # the chunked scan: no triangular solve (PR 34), its inverse a loop of
    # row updates over the 4 x 32 systems of the sub-chunks side by side
    assert "triangular_solve" not in text and "triangular-solve" not in text
    assert "tensor<64x64x128xf32>" in text
    assert "_step_kernel" not in text               # the scan keeps XLA
    assert "tensor<4x1x32x64x64xf32>" in text       # the scan's sub-chunks
