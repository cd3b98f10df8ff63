"""Compressed convolutional attention over a top-1 expert layer
(``models/cca_moe.py``) on the serving path, at a tiny size, float32, seeded
weights, on the CPU: the cache-less forward and the engine's own programs
against the benchmark's plain reference on logits, through the cache, across
prefill-chunk boundaries and slot reuse; the tail's rules; the steps of the
layer that are easy to get wrong (``u_{-1} = b0``, the value shift at
position 0, the top-1 weight); the two shares of an expert layer against
the uncut layer; the pool's pages AND tails; what is refused by name; and
both engine programs lowered for a TPU at the published widths."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmarks.reference import cca_moe as R  # noqa: E402
from distributed_training_sandbox_tpu.models import cca_moe as C  # noqa: E402
from distributed_training_sandbox_tpu.models import mla_moe as M  # noqa: E402
from distributed_training_sandbox_tpu.models import transformer as T  # noqa: E402
from distributed_training_sandbox_tpu.serving import ServingEngine  # noqa: E402
from distributed_training_sandbox_tpu.serving import engine as E  # noqa: E402
from distributed_training_sandbox_tpu.serving import kv_pool  # noqa: E402
from distributed_training_sandbox_tpu.serving.kv_pool import PagedKVPool  # noqa: E402
from tests.serving_blocks import FIELDS as BLOCK_FIELDS  # noqa: E402
from tests.serving_blocks import serve_logits  # noqa: E402

FIELDS = BLOCK_FIELDS["cca_moe"]
#: latent channels and tail width of the tiny model: (4 + 2) x 16, 2 C + hd
CH, TAIL = 96, 208


def make(seed=0, scale=2.0, **over):
    """Seeded weights, scaled as the benchmark scales them; the norms'
    weights and the router's biases (initialised 1 and 0) moved off their
    init, so that each is exercised, and the router's three small layers
    sharpened, so that at 64 wide a row's expert depends on the row."""
    fields = {**FIELDS, **over}
    cfg = T.TransformerConfig(**fields, dtype=jnp.float32, remat=False)
    params = jax.tree.map(lambda x: x * scale,
                          T.init_params(jax.random.key(seed), cfg))
    key = jax.random.key(seed + 100)

    def off_init(path, x):
        name = str(path[-1])
        k = jax.random.fold_in(key, sum(map(ord, str(path))))
        if "ln" in name or "final_norm" in name:
            return x + 0.3 * jax.random.normal(k, x.shape, x.dtype)
        if "br_" in name:
            return 0.05 * jax.random.normal(k, x.shape, x.dtype)
        if name.strip("[]'") in ("wr_1", "wr_2", "wr_3"):
            return 4.0 * x
        return x

    return fields, cfg, jax.tree_util.tree_map_with_path(off_init, params)


@pytest.fixture(scope="module")
def model():
    return make()


def test_the_block_is_selected_and_counted(model):
    _, cfg, params = model
    assert cfg.cca_moe and cfg.state_slots and cfg.linear_mixer is None
    assert not (cfg.mla_moe or cfg.gdn_hybrid or cfg.swa_moe or cfg.ssm_moe)
    assert cfg.block_module is C and cfg.held_experts == 4
    assert C.layer_kinds(cfg) == ("conv_full",) * 3
    assert C.latent_channels(cfg) == CH and C.tail_shape(cfg) == (TAIL,)
    lw = params["layers"][0]
    assert lw["w_qkv"].shape == (64, CH + 32)
    assert lw["conv0_w"].shape == (2, CH) and lw["conv0_b"].shape == (CH,)
    assert lw["conv1_w"].shape == (6, 2, 16, 16)
    assert lw["conv1_b"].shape == (CH,) and lw["k_temp"].shape == (2,)
    assert lw["k_temp"].dtype == jnp.float32
    assert lw["wr_down"].shape == (64, 32) and lw["wr_3"].shape == (32, 8)
    assert lw["we_gate"].shape == (4, 64, 32)
    assert not {"w_router", "ws_gate", "lm_head"} & (set(lw) | set(params))
    assert cfg.param_count() == sum(x.size for x in jax.tree.leaves(params))
    init = T.init_params(jax.random.key(0), cfg)["layers"][0]
    assert not np.any(np.asarray(init["br_3"]))
    assert C.K_TEMP_INIT > 2.0
    assert np.all(np.asarray(init["k_temp"]) == C.K_TEMP_INIT)
    assert C.COUNTERS == M.COUNTERS + ("conv_tail_slot_steps",)
    assert C.DEVICE_COUNTERS == C.COUNTERS and C.NOPE_KINDS == ()


def test_cacheless_forward_is_the_reference(model):
    fields, cfg, params = model
    ids = jax.random.randint(jax.random.key(1), (2, 37), 1, 256)
    with jax.default_matmul_precision("highest"):
        z = T.forward(params, ids, cfg)
    for b in range(2):
        want = R.logits_at(params, ids[b], jnp.arange(37), fields, block=37)
        np.testing.assert_allclose(z[b], want, atol=2e-4)
    assert float(jnp.std(z)) > 0.1


# ------------------------------------------------------- through the cache

def _pool(cfg, slots, page=8, seq=64):
    P = seq // page
    return PagedKVPool(cfg, slots * P + 1, page, n_slots=slots), P


def _serve_logits(*args, **kw):
    """``serving_blocks.serve_logits`` without the request's page row."""
    return serve_logits(*args, **kw)[:3]


def _reference_logits(params, fields, prompt, z):
    toks = np.asarray(jnp.argmax(z, axis=-1))
    seq = np.concatenate([prompt, toks[:-1]]).astype(np.int32)
    pos = len(prompt) - 1 + np.arange(len(toks))
    return R.logits_at(params, jnp.asarray(seq), jnp.asarray(pos), fields,
                       block=len(seq))


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernels"])
@pytest.mark.parametrize("n_prompt", [37, 32, 5, 1])
def test_engine_prefill_then_decode_is_the_reference_on_logits(
        model, kernel, n_prompt):
    """Prefill in chunks of 16 carrying the tail (37 spans three chunks,
    so the tail crosses two boundaries; 32 ends ON one),
    then six decode steps through tails and pages (gather path, or both
    paged kernels interpreted), against the reference's whole forward pass
    of the same tokens.  float32 everywhere; 3e-4 catches a lost or stale
    tail, a wrong page, mask, temperature or rotary dim and not the
    summation order."""
    fields, cfg, params = model
    prompt = np.random.default_rng(n_prompt).integers(
        1, 256, n_prompt).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        z, _, counted = _serve_logits(params, cfg, prompt, 7, kernel=kernel)
    np.testing.assert_allclose(z, _reference_logits(params, fields, prompt,
                                                    z), atol=3e-4)
    # six steps x three expert layers x one live row choosing 1 of 8
    a, held, touched, layer_steps, live = counted
    assert (a, layer_steps, live) == (6 * 3, 6 * 3, 6)
    assert 0 <= touched == held <= a


def test_an_inactive_slot_and_a_first_chunk_keep_to_their_own_tail(model):
    """Garbage in every slot's tail and in every page: the request's first
    chunk starts from zeros whatever its slot held, and the OTHER slots'
    tails and the pages that are not the request's are bit-unchanged by its
    prefill and by its decode steps (their rows have ``valid`` False)."""
    _, cfg, params = model
    prompt = np.random.default_rng(5).integers(1, 256, 21).astype(np.int32)
    pool, P = _pool(cfg, 3)
    junk = jax.tree.map(
        lambda a: jax.random.normal(jax.random.key(7), a.shape, a.dtype),
        (pool.bufs.k, pool.bufs.v, pool.bufs.conv))
    dirty = pool.bufs._replace(k=junk[0], v=junk[1], conv=junk[2])
    mine = np.asarray(pool.allocator.alloc(P))     # what _serve_logits grants
    others = np.setdiff1d(np.arange(1, 3 * P + 1), mine)
    for kernel in (False, True):
        with jax.default_matmul_precision("highest"):
            z0, _, _ = _serve_logits(params, cfg, prompt, 4, kernel=kernel)
            z1, bufs, _ = _serve_logits(params, cfg, prompt, 4,
                                        kernel=kernel, bufs=dirty)
        assert np.array_equal(np.asarray(z0), np.asarray(z1))
        assert bufs.state is None and len(bufs.conv) == 3
        for got, was in zip(bufs.conv, junk[2]):
            assert np.array_equal(np.asarray(got)[[0, 2]],
                                  np.asarray(was)[[0, 2]])
            assert not np.array_equal(np.asarray(got)[1], np.asarray(was)[1])
        for got, was in zip(bufs.k + bufs.v, junk[0] + junk[1]):
            assert np.array_equal(np.asarray(got)[others],
                                  np.asarray(was)[others])


def test_rows_past_the_prompts_end_leave_no_trace_in_the_tail(model):
    """A chunk whose prompt ends inside it hands on the tail its VALID rows
    leave: the same as the chunk cut at the prompt's end computes."""
    _, cfg, params = model
    lw = params["layers"][0]
    r = jax.random.normal(jax.random.key(2), (1, 16, 64))
    rope = C.rope_tables(jnp.arange(16)[None], cfg)
    tail = jax.random.normal(jax.random.key(3), (1, TAIL))
    ok = jnp.arange(16)[None] < 11
    *_, t_padded = C.attention_qkv(r, lw, cfg=cfg, rope=rope, tail=tail,
                                   valid=ok)
    *_, t_cut = C.attention_qkv(
        r[:, :11], lw, cfg=cfg, rope=(rope[0][:, :11], rope[1][:, :11]),
        tail=tail, valid=jnp.ones((1, 11), bool))
    assert np.array_equal(np.asarray(t_padded), np.asarray(t_cut))
    *_, t_none = C.attention_qkv(r, lw, cfg=cfg, rope=rope, tail=tail,
                                 valid=jnp.zeros((1, 16), bool))
    assert np.array_equal(np.asarray(t_none), np.asarray(tail))
    # the tail IS the last two latents and the last shifted value half
    zv = r[0, :11] @ lw["w_qkv"]
    np.testing.assert_allclose(t_cut[0, :CH], zv[9, :CH], atol=1e-6)
    np.testing.assert_allclose(t_cut[0, CH:2 * CH], zv[10, :CH], atol=1e-6)
    np.testing.assert_allclose(t_cut[0, 2 * CH:], zv[10, CH + 16:],
                               atol=1e-6)


@pytest.mark.parametrize("fault", [
    "shifted_half_from_the_current_token", "tail_zeroed_at_a_chunk_boundary",
    "temperature_left_out", "top1_weight_renormalised", "int8"])
def test_a_planted_fault_leaves_the_reference_on_logits(model, fault):
    """Each fault of ``tests/benchmark/cca_moe_faults.py`` (and int8
    projections, the fault that needs no code) moves the logits of
    prefill-in-chunks-then-decode off the reference by more than the 3e-4
    the sound program is held to.  The decode-step faults show from the
    second served token on and leave the first sound; the chunk boundary's
    shows in the first (a prompt of 37 crosses two boundaries).  The
    benchmark's check by tokens does not separate three of them on the chip
    (the configuration's ``check.why``): here they are held on logits."""
    import contextlib
    from tests.benchmark import cca_moe_faults
    fields, cfg, params = model
    plant = contextlib.nullcontext
    if fault == "int8":
        cfg = T.TransformerConfig(**{**fields, "matmul_precision": "int8"},
                                  dtype=jnp.float32, remat=False)
    else:
        plant = cca_moe_faults.FAULTS[fault][0]
    prompt = np.random.default_rng(37).integers(1, 256, 37).astype(np.int32)
    with plant(), jax.default_matmul_precision("highest"):
        z, _, _ = _serve_logits(params, cfg, prompt, 7, kernel=False)
    want = _reference_logits(params, fields, prompt, z)
    gap = np.max(np.abs(np.asarray(z) - np.asarray(want)), axis=-1)
    assert gap.max() > 1e-3, gap
    if fault not in ("int8", "tail_zeroed_at_a_chunk_boundary"):
        assert gap[0] < 3e-4 and gap[1:].max() > 1e-3, gap
    if fault == "tail_zeroed_at_a_chunk_boundary":
        assert gap[0] > 1e-3, gap


# ------------------------------------------- the steps that are easy to miss

def test_the_row_before_the_first_is_stage_0_of_two_zero_rows(model):
    """Step 4: the sequence is padded ONCE, before stage 0, so stage 1's
    row 0 reads ``u_{-1} = b0`` and not 0."""
    _, cfg, params = model
    lw = params["layers"][0]
    z = jax.random.normal(jax.random.key(4), (5, CH))
    y = R.conv_latents(z, lw, 16)
    a, b0 = lw["conv0_w"], lw["conv0_b"]
    A, b1 = lw["conv1_w"], lw["conv1_b"].reshape(6, 16)
    u0 = a[1] * z[0] + b0
    want = jnp.einsum("gc,gcd->gd", b0.reshape(6, 16), A[:, 0]) \
        + jnp.einsum("gc,gcd->gd", u0.reshape(6, 16), A[:, 1]) + b1
    np.testing.assert_allclose(y[0], want, atol=1e-5)
    padded_twice = jnp.einsum("gc,gcd->gd", u0.reshape(6, 16), A[:, 1]) + b1
    assert float(jnp.max(jnp.abs(y[0] - padded_twice))) > 1e-2
    # the program, from a tail of zeros: q' and k' before the norms are
    # y + the q-k mean, so compare through the reference's own attention
    r = jax.random.normal(jax.random.key(5), (1, 5, 64))
    q, k, v, gate, _ = C.attention_qkv(
        r, lw, cfg=cfg, rope=C.rope_tables(jnp.arange(5)[None], cfg),
        tail=jnp.zeros((1, TAIL)), valid=jnp.ones((1, 5), bool))
    assert gate is None
    zr = r[0] @ lw["w_qkv"][:, :CH]
    yr = R.conv_latents(zr, lw, 16)
    mq = 0.5 * (zr[:, :64].reshape(5, 2, 2, 16)
                + zr[:, 64:].reshape(5, 2, 1, 16))
    qr = 4.0 * R._unit(yr[:, :4] + mq.reshape(5, 4, 16))
    kr = 4.0 * R._unit(yr[:, 4:] + jnp.mean(mq, axis=2)) \
        * lw["k_temp"][:, None]
    fields = {"partial_rotary_factor": 0.5, "rope_theta": 5e6}
    np.testing.assert_allclose(q[0], R._rope(qr, fields), atol=2e-5)
    np.testing.assert_allclose(k[0], R._rope(kr, fields), atol=2e-5)


def test_the_value_shift_at_position_0_and_after(model):
    """Step 6: KV head 0 holds the token's own ``r_t wv1``, KV head 1 the
    PREVIOUS token's ``r_{t-1} wv2``: zeros at position 0 of a request,
    the tail's last ``hd`` where rows came before."""
    _, cfg, params = model
    lw = params["layers"][1]
    r = jax.random.normal(jax.random.key(6), (2, 4, 64))
    rope = C.rope_tables(jnp.arange(4)[None], cfg)
    tail = jnp.zeros((2, TAIL)).at[1, 2 * CH:].set(3.0)
    _, _, v, _, _ = C.attention_qkv(r, lw, cfg=cfg, rope=rope, tail=tail,
                                    valid=jnp.ones((2, 4), bool))
    v1, v2 = r @ lw["w_qkv"][:, CH:CH + 16], r @ lw["w_qkv"][:, CH + 16:]
    np.testing.assert_allclose(v[:, :, 0], v1, atol=1e-6)
    np.testing.assert_allclose(v[:, 1:, 1], v2[:, :-1], atol=1e-6)
    assert not np.any(np.asarray(v[0, 0, 1]))
    assert np.all(np.asarray(v[1, 0, 1]) == 3.0)


def test_the_top_1_weight_is_the_routers_probability_itself(model):
    """``route`` under this block returns ``p_e`` for the one chosen
    expert (0 where it is not held); told to renormalise, 1."""
    _, cfg, params = model
    lw = params["layers"][0]
    rows = jax.random.normal(jax.random.key(8), (9, 64))
    with jax.default_matmul_precision("highest"):
        logits = C.router_logits(rows, lw)
        w_held, idx = M.route(rows, None, cfg, logits=logits)
        p = R.router_probs(rows, lw)
    np.testing.assert_allclose(jax.nn.softmax(logits, -1), p, atol=1e-6)
    assert idx.shape == (9, 1)
    assert np.array_equal(np.asarray(idx[:, 0]), np.asarray(jnp.argmax(p, -1)))
    p_e = np.asarray(jnp.max(p, -1))
    assert p_e.max() < 0.9 and p_e.std() > 1e-3   # no constant, not 1
    held = np.asarray(idx[:, 0]) - cfg.expert_offset
    for t in range(9):
        want = np.zeros(4, np.float32)
        if 0 <= held[t] < 4:
            want[held[t]] = p_e[t]
        np.testing.assert_allclose(w_held[t], want, rtol=1e-6)
    assert 0 < (held >= 0).sum() < 9 or (np.asarray(w_held) > 0).any()
    # renormalised: the chosen weight is 1 whatever the router says
    try:
        C.NORM_TOPK_PROB = True
        w_norm, _ = M.route(rows, None, cfg, logits=logits)
    finally:
        C.NORM_TOPK_PROB = False
    assert set(np.unique(np.asarray(w_norm)).round(6)) <= {0.0, 1.0}


@pytest.mark.parametrize("block", ["mla_moe", "gdn_moe", "swa_moe",
                                   "ssm_moe"])
def test_the_accepted_blocks_weights_are_renormalised_to_the_bit(block):
    """``route`` for a block that declares nothing: the chosen scores
    renormalised to ``routed_scaling_factor``, as before the declaration
    existed, bit for bit; handed the same logits it computes itself, the
    same again."""
    from jax import lax
    from tests import serving_blocks
    _, cfg, params = serving_blocks.make(block, seed=2, scale=2.0)
    lw = next(lw for lw in params["layers"] if "w_router" in lw)
    rows = jax.random.normal(jax.random.key(9), (11, 64))
    kw = {"bias": lw["router_bias"]} if "router_bias" in lw else {}
    w_held, idx = M.route(rows, lw["w_router"], cfg, **kw)
    with jax.default_matmul_precision("highest"):
        logits = rows @ lw["w_router"]
    s = jax.nn.sigmoid(logits) if cfg.block_module.ROUTER_SCORING \
        == "sigmoid" else jax.nn.softmax(logits, axis=-1)
    _, want_idx = lax.top_k(s + kw.get("bias", 0.0), cfg.num_experts_per_tok)
    top = jnp.take_along_axis(s, want_idx, axis=-1)
    w = cfg.routed_scaling_factor * top \
        / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    held = cfg.expert_offset + jnp.arange(cfg.held_experts)
    want = jnp.sum(jnp.where(want_idx[:, :, None] == held[None, None, :],
                             w[:, :, None], 0.0), axis=1)
    assert np.array_equal(np.asarray(idx), np.asarray(want_idx))
    assert np.array_equal(np.asarray(w_held), np.asarray(want))
    again, _ = M.route(rows, None, cfg, logits=logits, **kw)
    assert np.array_equal(np.asarray(again), np.asarray(w_held))
    assert not hasattr(cfg.block_module, "NORM_TOPK_PROB")


def test_an_expert_layer_without_a_shared_expert_is_its_routed_sum(model):
    fields, cfg, params = model
    lw = params["layers"][2]
    r2 = jax.random.normal(jax.random.key(10), (2, 7, 64))
    with jax.default_matmul_precision("highest"):
        m, counts = M.expert_mlp(r2, lw, cfg=cfg)
        for b in range(2):
            np.testing.assert_allclose(m[b], R.moe(r2[b], lw, fields),
                                       atol=2e-5)
    assert int(counts[0]) == 14 and 0 < int(counts[1]) <= 14


def test_two_shares_add_up_to_the_uncut_layer():
    """The held experts tied to the model: with ``expert_offset`` 0 and 8,
    two shares of 8 held experts add up to the uncut 16-expert layer (no
    shared expert to count once), and each share is its reference
    share's."""
    fields, cfg, _ = make(num_experts=16, router_width=16, expert_offset=0)
    whole = T.init_params(jax.random.key(3), cfg)["layers"][0]
    whole = jax.tree.map(lambda x: 8.0 * x, whole)
    r2 = jax.random.normal(jax.random.key(4), (1, 23, 64))
    with jax.default_matmul_precision("highest"):
        want = R.moe(r2[0], whole, fields)
        uncut, counts = M.expert_mlp(r2, whole, cfg=cfg)
        np.testing.assert_allclose(uncut[0], want, atol=2e-5)
        assert int(counts[0]) == int(counts[1]) == 23   # every choice held
        total, held = jnp.zeros_like(want), 0
        for offset in (0, 8):
            share_fields = {**fields, "num_experts": 8,
                            "expert_offset": offset}
            share_cfg = T.TransformerConfig(**share_fields,
                                            dtype=jnp.float32, remat=False)
            lw = {**whole, **{k: whole[k][offset:offset + 8]
                              for k in ("we_gate", "we_up", "we_down")}}
            m, counts = M.expert_mlp(r2, lw, cfg=share_cfg)
            np.testing.assert_allclose(m[0], R.moe(r2[0], lw, share_fields),
                                       atol=2e-5)
            total, held = total + m[0], held + int(counts[1])
            assert 0 < int(counts[1]) < 23
    np.testing.assert_allclose(total, want, atol=1e-4)
    assert held == 23 and float(jnp.max(jnp.abs(want))) > 0.1


# ------------------------------------------------------ the pool, the engine

def test_the_pool_sizes_pages_and_tails_from_the_kinds_alone(model):
    _, cfg, _ = model
    assert kv_pool.layer_kinds(cfg) == ("conv_full",) * 3
    assert kv_pool.paged_layers(cfg) == 3
    assert kv_pool.row_layout(cfg) == ((2, 16), True)
    assert not kv_pool.slab_pool(cfg)
    assert kv_pool.token_row_bytes(cfg) == 2 * 2 * 16 * 4
    assert kv_pool.slot_state_bytes(cfg) == 3 * TAIL * 4
    pool = PagedKVPool(cfg, 9, 8, n_slots=2)
    assert [a.shape for a in pool.bufs.k] == [(9, 8, 2, 16)] * 3
    assert [a.shape for a in pool.bufs.v] == [(9, 8, 2, 16)] * 3
    assert pool.bufs.state is None
    assert [a.shape for a in pool.bufs.conv] == [(2, TAIL)] * 3
    assert pool.state_bytes == 2 * 3 * TAIL * 4
    with pytest.raises(ValueError, match="pass n_slots >= 1"):
        PagedKVPool(cfg, 9, 8)
    with pytest.raises(ValueError, match="neither kv_quant nor a mesh"):
        PagedKVPool(cfg, 9, 8, n_slots=2, kv_quant=True)
    # at the published widths a row of two heads of 128 stays 4-D, unpadded
    big = T.TransformerConfig(**{
        **FIELDS, "hidden_size": 2048, "num_attention_heads": 8,
        "head_dim": 128, "dtype": jnp.bfloat16})
    assert kv_pool.row_layout(big) == ((2, 128), True)
    assert kv_pool.pool_shape(big, 5, 16) == (5, 16, 2, 128)
    assert kv_pool.token_row_bytes(big) == 1024
    assert kv_pool.slot_state_bytes(big) == 3 * (2 * 1280 + 128) * 2


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernels"])
def test_the_engine_serves_the_reference_and_counts(model, kernel):
    """Five requests over two slots (a slot is granted again: its tail
    starts from zeros), prompts that span up to three chunks: every served
    token is the reference's greedy token, and the counters add up."""
    from tests.serving_blocks import reference_tokens
    fields, cfg, params = model
    eng = ServingEngine(params, cfg, max_batch=2, page_size=8,
                        max_seq_len=64, prefill_chunk=16,
                        paged_kernel=kernel)
    rng = np.random.default_rng(3)
    sizes = ((37, 6), (19, 3), (7, 9), (33, 2), (16, 5))
    reqs = [eng.submit(rng.integers(1, 256, size=n).astype(np.int32),
                       max_new_tokens=new) for n, new in sizes]
    eng.run()
    s = eng.stats
    for req, (_, new) in zip(reqs, sizes):
        assert len(req.tokens) == new
        assert req.tokens == reference_tokens("cca_moe", fields, params,
                                              req.prompt, req.tokens)
    assert s["admitted"] == 5 > eng.max_batch
    live = sum(new - 1 for _, new in sizes)
    assert s["conv_tail_slot_steps"] == live
    assert s["moe_assignments"] == 3 * live
    assert s["moe_expert_layer_steps"] == 3 * s["decode_steps"]
    assert 0 < s["moe_experts_touched"] <= s["moe_assignments_held"] \
        < s["moe_assignments"]          # 4 of the router's 8 are held
    assert "state_resets" not in s and "lin_scan_rows" not in s
    assert (s["decode_inplace_steps"] > 0) == kernel
    assert (s["prefill_inplace_chunks"] == s["prefill_chunks"]) == kernel
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
                       match=f"compressed convolutional attention block.*"
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
                       match="compressed convolutional attention block"
                             ".*not built"):
        call()


@pytest.mark.parametrize("over,match", [
    ({"moe_intermediate_size": 0}, r"needs \['moe_intermediate_size'\]"),
    ({"router_hidden_size": 0}, r"needs \['router_hidden_size'\]"),
    ({"router_width": 6}, "held experts 4..7 are not among the router's 6"),
    ({"num_experts_per_tok": 2}, "num_experts_per_tok=1 only"),
    ({"norm_topk_prob": True}, "norm_topk_prob=False only"),
    ({"intermediate_size": 160}, "intermediate_size=None only"),
    ({"cca_time1": 3}, "cca_time1=2 only"),
    ({"cca_time0": 4}, "cca_time0=2 only"),
    ({"num_key_value_heads": 4}, "num_key_value_heads=2 only"),
    ({"partial_rotary_factor": 0.2}, "even number of rotary dims"),
    ({"tie_word_embeddings": False}, "tie_word_embeddings=True only"),
    ({"routed_scaling_factor": 2.5}, "routed_scaling_factor=1.0 only"),
    ({"n_routed_experts": 4}, "n_routed_experts=0 only"),
    ({"num_attention_heads": 3}, "a multiple of num_key_value_heads"),
])
def test_a_variant_the_block_does_not_build_is_refused_by_name(over, match):
    with pytest.raises(ValueError, match=match):
        T.TransformerConfig(**{**FIELDS, **over})


# ------------------------------------- for a TPU, at the published widths

def test_both_programs_lower_for_tpu_at_published_widths(monkeypatch):
    """The decode step and the prefill chunk at the cell's widths and pool
    shape (four layers), lowered FOR a TPU on this host: the paged decode
    kernel, the flash prefill kernel and the grouped experts' kernel are in
    them, one call a layer each."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    B, page, P, layers = 64, 16, 256, 4
    cfg = T.TransformerConfig(
        vocab_size=262272, hidden_size=2048, intermediate_size=None,
        num_hidden_layers=layers, num_attention_heads=8,
        num_key_value_heads=2, head_dim=128, rms_norm_eps=1e-5,
        rope_theta=5e6, partial_rotary_factor=0.5, tie_word_embeddings=True,
        nope_interval=0, cca_time0=2, cca_time1=2, router_hidden_size=256,
        num_experts=16, router_width=16, num_experts_per_tok=1,
        moe_intermediate_size=2048, dtype=jnp.bfloat16, remat=False)
    assert cfg.param_count() == 4 * 207_566_354 + 262272 * 2048 + 2048
    sd = jax.ShapeDtypeStruct
    params = jax.eval_shape(lambda: T.init_params(jax.random.key(0), cfg))
    bufs = jax.eval_shape(
        lambda: PagedKVPool(cfg, B * P + 1, page, n_slots=B).bufs)
    i32 = lambda *shape: sd(shape, jnp.int32)  # noqa: E731
    dec = E.make_serve_decode_step(cfg, paged_kernel=True).trace(
        bufs, params, i32(B, P), i32(B), i32(B), i32(B), sd((B,), jnp.bool_),
        i32(5 + 4 * B)).lower(lowering_platforms=("tpu",)).as_text()
    pre = E.make_serve_prefill_step(cfg, paged_kernel=True).trace(
        bufs, params, i32(1, P), i32(1, 256), i32(), i32(),
        i32()).lower(lowering_platforms=("tpu",)).as_text()
    for text in (dec, pre):
        assert text.count("tpu_custom_call") >= 2
        assert "cca_conv" in text or "stablehlo" in text
