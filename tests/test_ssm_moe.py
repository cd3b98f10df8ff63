"""The Mamba-2 + attention block with held experts (``models/ssm_moe.py``)
on the serving path, at a tiny size, float32, seeded weights, on the CPU:
the two forms of the recurrence against each other and against the
reference's token scan, the step kernel (interpreted) against the XLA form,
the cache-less forward and the engine's own programs against the
benchmark's plain reference on logits, the four shares of an expert layer
against the uncut layer, each multiplier and each term shown to matter,
state slots and counters, what is refused by name, and both engine programs
lowered for a TPU at the published widths."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmarks.reference import ssm_moe as R  # noqa: E402
from distributed_training_sandbox_tpu.models import mla_moe as M  # noqa: E402
from distributed_training_sandbox_tpu.models import ssm_moe as S  # noqa: E402
from distributed_training_sandbox_tpu.models import transformer as T  # noqa: E402
from distributed_training_sandbox_tpu.ops.ssm_step import (  # noqa: E402
    lane_group, ssm_decode_step, step_kernel_takes)
from distributed_training_sandbox_tpu.serving import ServingEngine  # noqa: E402
from distributed_training_sandbox_tpu.serving import engine as E  # noqa: E402
from distributed_training_sandbox_tpu.serving.kv_pool import (  # noqa: E402
    PagedKVPool, paged_layers, pool_shape, row_layout, slab_pool,
    slot_state_bytes)
from tests.serving_blocks import FIELDS as BLOCK_FIELDS  # noqa: E402
# one request's chunked prefill and decode through the engine's own cores,
# tapped for logits: the hybrids' helper serves any block with state slots
from tests.test_gdn_moe import _pool, _serve_logits  # noqa: E402

FIELDS = BLOCK_FIELDS["ssm_moe"]


def make(seed=0, scale=2.0, **over):
    """Seeded weights, scaled as the benchmark scales them, the norm
    weights and ``Dskip`` moved off their init of 1."""
    fields = {**FIELDS, **over}
    cfg = T.TransformerConfig(**fields, dtype=jnp.float32, remat=False)
    params = jax.tree.map(lambda x: x * scale,
                          T.init_params(jax.random.key(seed), cfg))
    key = jax.random.key(seed + 100)

    def off_one(path, x):
        name = str(path[-1])
        if "norm" in name or "Dskip" in name:
            k = jax.random.fold_in(key, sum(map(ord, str(path))))
            return 1.0 + 0.3 * jax.random.normal(k, x.shape, x.dtype)
        return x

    return fields, cfg, jax.tree_util.tree_map_with_path(off_one, params)


@pytest.fixture(scope="module")
def model():
    return make()


#: how far logits may lie from the reference's, in standard deviations of
#: the reference's logits (about 0.006 at this size: the tied head reads a
#: small embedding).  float32 everywhere: the paths differ from the
#: reference in summation order (chunked scan, online softmax), measured
#: 1e-5 of a deviation; 5e-4 catches a lost tail, a stale state, a wrong
#: page, scale or multiplier and not the rounding
REL = 5e-4


def assert_logits(z, want, rel=REL):
    assert float(jnp.std(want)) > 1e-3
    np.testing.assert_allclose(z, want, rtol=0,
                               atol=rel * float(jnp.std(want)))


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Blocks of 4 rows, so that a 16-row prefill chunk scans four of them
    in sequence and a prompt ends inside one."""
    monkeypatch.setattr(S, "SCAN_BLOCK", 4)


def test_the_block_is_selected_and_counted(model):
    _, cfg, params = model
    assert cfg.ssm_moe and cfg.state_slots and not (
        cfg.mla_moe or cfg.gdn_hybrid or cfg.gdn_moe or cfg.swa_moe)
    assert cfg.block_module is S and cfg.linear_mixer is S
    assert cfg.held_experts == 4
    assert S.layer_kinds(cfg) == ("linear", "linear", "full", "linear")
    ssm, attn = params["layers"][0], params["layers"][2]
    assert ssm["w_z"].shape == (64, 128) and ssm["w_xbc"].shape == (64, 160)
    assert ssm["w_dt"].shape == (64, 8) and ssm["conv_w"].shape == (4, 160)
    assert ssm["conv_b"].shape == (160,) and ssm["gate_norm"].shape == (128,)
    assert ssm["A_log"].shape == ssm["dt_bias"].shape == (8,)
    assert ssm["Dskip"].shape == (8,) and ssm["w_out"].shape == (128, 64)
    assert attn["wq"].shape == (64, 64) and attn["wk"].shape == (64, 32)
    assert "q_norm" not in attn and "w_z" not in attn
    for lw in (ssm, attn):
        assert lw["w_router"].shape == (64, 12)
        assert lw["we_gate"].shape == (4, 64, 32)
        assert lw["ws_gate"].shape == (64, 48) and "ws_sigmoid" not in lw
        assert {"input_norm", "post_attn_norm"} <= set(lw)
    assert "lm_head" not in params                          # tied
    assert cfg.param_count() == sum(x.size for x in jax.tree.leaves(params))
    assert S.state_shape(cfg) == (8, 16, 16) and S.slot_shape(cfg) == (16, 128)
    assert S.tail_shape(cfg) == (3, 160) and S.conv_channels(cfg) == 160
    assert S.attention_scale(cfg) == 0.03125 != 16 ** -0.5
    init = T.init_params(jax.random.key(0), cfg)["layers"][0]
    A = np.exp(np.asarray(init["A_log"]))
    dt = np.log1p(np.exp(np.asarray(init["dt_bias"])))
    assert (A >= 1).all() and (A <= 16).all()
    assert (dt >= 1e-3 * 0.99).all() and (dt <= 1e-1 * 1.01).all()
    assert np.all(np.asarray(init["Dskip"]) == 1)
    assert np.any(np.asarray(init["conv_b"]))               # WITH bias


def test_layer_types_is_the_pattern_and_a_list_hashes(model):
    _, cfg, _ = model
    again = T.TransformerConfig(**{**FIELDS, "layer_types": list(
        FIELDS["layer_types"])}, dtype=jnp.float32, remat=False)
    assert again == cfg and hash(again) == hash(cfg)
    assert S.layer_kinds(again) == ("linear", "linear", "full", "linear")


# ------------------------------------------ the two forms of the recurrence

def _inputs(seed, B=2, S_=13, n=8, hd=16, ds=16):
    ks = jax.random.split(jax.random.key(seed), 6)
    xd = 0.1 * jax.random.normal(ks[0], (B, S_, n, hd))
    Bm = jax.random.normal(ks[1], (B, S_, ds))
    Cm = jax.random.normal(ks[2], (B, S_, ds))
    g = -jax.random.uniform(ks[3], (B, S_, n), minval=0.0, maxval=2.0)
    skip = jax.random.normal(ks[4], (B, S_, n, hd))
    s0 = jax.random.normal(ks[5], (B, ds, n, hd))           # NON-zero state
    return xd, Bm, Cm, g, skip, s0


def recurrence64(xd, Bm, Cm, g, skip, s0):
    """The recurrence of the module docstring token by token, in float64
    numpy, on the state as (B, n, hd, ds): the oracle of both forms."""
    xd, Bm, Cm, g, skip = (np.asarray(a, np.float64)
                           for a in (xd, Bm, Cm, g, skip))
    s = np.asarray(s0, np.float64).transpose(0, 2, 3, 1)    # (B, n, hd, ds)
    out = []
    for t in range(xd.shape[1]):
        s = np.exp(g[:, t])[:, :, None, None] * s \
            + xd[:, t][..., None] * Bm[:, t][:, None, None, :]
        out.append(np.einsum("bnps,bs->bnp", s, Cm[:, t]) + skip[:, t])
    return np.stack(out, 1), s.transpose(0, 3, 1, 2)


def _token_by_token(xd, Bm, Cm, g, skip, s0, step=S.recurrent_step):
    out, s = [], S.pack_state(s0)
    for t in range(xd.shape[1]):
        o, s = step(xd[:, t], Bm[:, t], Cm[:, t], g[:, t], skip[:, t], s)
        out.append(o)
    return jnp.stack(out, axis=1), S.unpack_state(s, xd.shape[2])


@pytest.mark.parametrize("block", [4, 16])
@pytest.mark.parametrize("zero_state", [False, True], ids=["carried", "zero"])
@pytest.mark.parametrize("rows", [13, 16, 1, 37])
def test_the_two_forms_agree(monkeypatch, rows, zero_state, block):
    """Token step, chunked scan at blocks of 4 and 16 (a last block that
    is part of one) and the recurrence in float64, from a carried NON-zero
    state and from zeros."""
    monkeypatch.setattr(S, "SCAN_BLOCK", block)
    ins = _inputs(1, S_=rows)
    if zero_state:
        ins = ins[:-1] + (jnp.zeros_like(ins[-1]),)
    o_want, s_want = recurrence64(*ins)
    with jax.default_matmul_precision("highest"):
        o, s = S.chunked_scan(*ins)
        o_t, s_t = _token_by_token(*ins)
    np.testing.assert_allclose(o_t, o_want, atol=2e-5)
    np.testing.assert_allclose(s_t, s_want, atol=2e-5)
    np.testing.assert_allclose(o, o_want, atol=5e-5)
    np.testing.assert_allclose(s, s_want, atol=5e-5)


def test_the_block_size_is_arithmetic(monkeypatch):
    """The result does not depend on ``mamba_chunk_size``: 300 rows at the
    published 256 (one block and a part) against blocks of 4."""
    ins = _inputs(2, B=1, S_=300)
    with jax.default_matmul_precision("highest"):
        o4, s4 = S.chunked_scan(*ins)
        monkeypatch.setattr(S, "SCAN_BLOCK", 256)
        o256, s256 = S.chunked_scan(*ins)
    o_want, s_want = recurrence64(*ins)
    for got, want in ((o4, o_want), (o256, o_want), (s4, s_want),
                      (s256, s_want)):
        np.testing.assert_allclose(got, want, atol=2e-4)


def test_decay_applied_after_the_update_is_another_recurrence():
    """The order is visible to the test: ``a (S + x (x) B)`` instead of
    ``a S + x (x) B`` moves the outputs by 10% of their size."""
    xd, Bm, Cm, g, skip, s0 = _inputs(3)
    o_want, _ = recurrence64(xd, Bm, Cm, g, skip, s0)

    def late(xd, Bm, Cm, g, skip, state):
        o, s = S.recurrent_step(xd, Bm, Cm, jnp.zeros_like(g),
                                jnp.zeros_like(skip), state)
        a = jnp.repeat(jnp.exp(g), xd.shape[-1], axis=-1)
        return (o * a.reshape(o.shape) + skip, a[:, None] * s)

    o, _ = _token_by_token(xd, Bm, Cm, g, skip, s0, step=late)
    assert float(np.max(np.abs(np.asarray(o) - o_want))) \
        > 0.1 * float(np.std(o_want))


@pytest.mark.parametrize("dims", [(8, 16, 16), (4, 64, 8), (64, 64, 16),
                                  (3, 16, 24)],
                         ids=lambda d: "x".join(map(str, d)))
def test_the_step_kernel_is_the_xla_form(dims):
    """``ops/ssm_step.py`` interpreted against ``recurrent_step``'s XLA
    form: a slot narrower than a lane group (one grid step a slot), one of
    two groups (64 heads of 64: 4,096 lanes), a batch that is no whole row
    block; dead slots (g = xd = 0) bit-unchanged and given o = 0."""
    n, hd, ds = dims
    xd, Bm, Cm, g, skip, s0 = _inputs(4, B=5, S_=1, n=n, hd=hd, ds=ds)
    xd, Bm, Cm, g, skip = (a[:, 0] for a in (xd, Bm, Cm, g, skip))
    dead = jnp.array([False, True, False, False, True])
    g = jnp.where(dead[:, None], 0.0, g)
    xd = jnp.where(dead[:, None, None], 0.0, xd)
    s0 = S.pack_state(s0)
    o_want, s_want = S.recurrent_step(xd, Bm, Cm, g, jnp.zeros_like(skip), s0)
    o, s = ssm_decode_step(xd, Bm, Cm, g, s0, interpret=True)
    live = ~np.asarray(dead)
    np.testing.assert_allclose(np.asarray(o)[live], np.asarray(o_want)[live],
                               atol=1e-5)
    np.testing.assert_allclose(s, s_want, atol=1e-5)
    assert np.array_equal(np.asarray(s)[np.asarray(dead)],
                          np.asarray(s0)[np.asarray(dead)])    # bitwise
    assert not np.any(np.asarray(o)[np.asarray(dead)])


@pytest.mark.parametrize("dead", [(True, True, True), (True, False, True),
                                  (False, True, True), (True, True, False)],
                         ids=lambda d: "".join("d" if x else "L" for x in d))
def test_dead_slots_before_between_and_after_the_live_ones(dead):
    """The visit list over two lane groups: nothing live at all, a dead
    slot first, last, and both: no dead state moves a bit."""
    xd, Bm, Cm, g, skip, s0 = _inputs(5, B=3, S_=1, n=64, hd=64, ds=8)
    xd, Bm, Cm, g = (a[:, 0] for a in (xd, Bm, Cm, g))
    assert lane_group(64 * 64) == 2048
    dead = jnp.array(dead)
    g = jnp.where(dead[:, None], 0.0, g)
    xd = jnp.where(dead[:, None, None], 0.0, xd)
    s0 = S.pack_state(s0)
    _, s_want = S.recurrent_step(xd, Bm, Cm, g, jnp.zeros_like(xd), s0)
    _, s = ssm_decode_step(xd, Bm, Cm, g, s0, interpret=True)
    np.testing.assert_allclose(s, s_want, atol=1e-5)
    assert np.array_equal(np.asarray(s)[np.asarray(dead)],
                          np.asarray(s0)[np.asarray(dead)])


def test_the_lane_group_and_the_shapes_the_kernel_compiles_for():
    assert lane_group(128 * 64) == 2048 and lane_group(128) == 128
    assert step_kernel_takes(128, 64, 128)          # the published widths
    assert not step_kernel_takes(3, 16, 24)         # 48 lanes: interpret only
    assert not step_kernel_takes(128, 64, 12)       # ds in whole sublanes


def test_padding_rows_and_inactive_slots_change_no_state_bit():
    xd, Bm, Cm, g, skip, s0 = _inputs(6, S_=16)
    keep = (jnp.arange(16) < 9)[None, :, None]
    g, xd = jnp.where(keep, g, 0.0), jnp.where(keep[..., None], xd, 0.0)
    with jax.default_matmul_precision("highest"):
        _, s = S.chunked_scan(xd, Bm, Cm, g, skip, s0)
        _, s9 = S.chunked_scan(xd[:, :9], Bm[:, :9], Cm[:, :9], g[:, :9],
                               skip[:, :9], s0)
    np.testing.assert_allclose(s, s9, atol=1e-6)
    for step in (S.recurrent_step,
                 lambda xd, b, c, g, skip, s: ssm_decode_step(
                     xd, b, c, g, s, interpret=True)):
        _, s1 = step(xd[:, 12], Bm[:, 12], Cm[:, 12], g[:, 12], skip[:, 12],
                     S.pack_state(s0))
        assert np.array_equal(np.asarray(s1), np.asarray(S.pack_state(s0)))


# ------------------------------------------------- against the reference

def test_cacheless_forward_is_the_reference(model):
    fields, cfg, params = model
    ids = jax.random.randint(jax.random.key(1), (2, 37), 1, 256)
    with jax.default_matmul_precision("highest"):
        z = T.forward(params, ids, cfg)
    for b in range(2):
        assert_logits(z[b], R.logits_at(params, ids[b], jnp.arange(37),
                                        fields, block=37))


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
    """Prefill in chunks of 16 carrying state and conv tail (a prompt that
    spans three chunks and ends inside one, one that ends a block past a
    chunk, a whole chunk, a part of one), then six decode steps through
    state slots and pages (gather path and XLA step, or all three kernels
    interpreted), against the reference's whole forward pass of the same
    tokens, to ``REL``."""
    fields, cfg, params = model
    prompt = np.random.default_rng(n_prompt).integers(
        1, 256, n_prompt).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        z, _, counted = _serve_logits(params, cfg, prompt, 7, kernel=kernel)
    assert_logits(z, _reference_logits(params, fields, prompt, z))
    # six steps x four expert layers x one live row choosing 3 of 12
    a, held, touched, layer_steps, live = counted
    assert (a, layer_steps, live) == (6 * 4 * 3, 6 * 4, 6)
    assert 0 <= touched == held <= a


def test_a_granted_slot_never_inherits_a_state(model):
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


#: what is left out or replaced -> the least factor over the sound
#: program's ``REL`` by which the logits then miss the reference
MATTERS = {
    "embedding_multiplier": 1000, "residual_multiplier": 1000,
    "attention_multiplier": 1000, "logits_scaling": 1000, "conv_bias": 1000,
    "Dskip": 1000, "rotary": 100,
}


@pytest.mark.parametrize("what", sorted(MATTERS))
def test_each_multiplier_and_term_matters(model, what):
    """The program with one multiplier at 1 (the attention's at
    ``1/sqrt(hd)``), the conv's bias or ``Dskip`` zeroed, or a rotary
    embedding applied, against the reference as published: the logits of
    the cache-less forward miss by the stated factor over the ``REL`` the
    sound program is held to (measured: 4,000 to 27,000 x; a rotary
    embedding 470 x).  The attention layer's four
    matrices are 4 x the model's, so that its probabilities are not flat,
    its output is no small part of the residual stream, and a scale or a
    rotation shows."""
    fields, cfg, params = model
    params = {**params, "layers": tuple(
        {**lw, **{k: 4 * lw[k] for k in ("wq", "wk", "wv", "wo")}}
        if "wq" in lw else lw for lw in params["layers"])}
    sound = params
    ids = jax.random.randint(jax.random.key(2), (1, 29), 1, 256)
    if what == "rotary":
        patch = pytest.MonkeyPatch()
        patch.setattr(S, "rope_tables", lambda pos, cfg: E._ragged_rope_tables(
            pos, cfg.resolved_head_dim, 1e4))
        qkv = S.attention_qkv

        def rotated(r, layer, *, cfg, rope=None):
            q, k, v, gate = qkv(r, layer, cfg=cfg)
            return M._rope(q, *rope), M._rope(k, *rope), v, gate

        patch.setattr(S, "attention_qkv", rotated)
    elif what in ("conv_bias", "Dskip"):
        leaf = {"conv_bias": "conv_b", "Dskip": "Dskip"}[what]
        params = {**params, "layers": tuple(
            {**lw, leaf: jnp.zeros_like(lw[leaf])} if leaf in lw else lw
            for lw in params["layers"])}
    else:
        one = {"attention_multiplier": 0.0}.get(what, 1.0)
        cfg = T.TransformerConfig(**{**fields, what: one},
                                  dtype=jnp.float32, remat=False)
    try:
        with jax.default_matmul_precision("highest"):
            z = T.forward(params, ids, cfg)[0]
    finally:
        if what == "rotary":
            patch.undo()
    want = R.logits_at(sound, ids[0], jnp.arange(29), fields, block=29)
    miss = float(jnp.max(jnp.abs(z - want))) / (REL * float(jnp.std(want)))
    assert miss > MATTERS[what]


# -------------------------------------------------------- the share test

def test_four_shares_add_up_to_the_uncut_layer():
    """Expert parallelism's cut, tied to the model, for a WHOLE layer: the
    routed parts that the program computes as each of the 4 ranks (18 of
    72 experts a rank here as published, 3 of 12 at this size; weights the
    softmax over the CHOSEN experts, held or not) plus the mixer and the
    shared expert, which every rank computes alike, counted once, are the
    uncut 12-expert reference layer's output."""
    fields, cfg, _ = make(num_local_experts=12, router_width=12,
                          expert_offset=0, num_experts_per_tok=5)
    whole = jax.tree.map(lambda x: 3.0 * x, T.init_params(
        jax.random.key(3), cfg)["layers"][0])
    x = jax.random.normal(jax.random.key(4), (1, 11, 64))
    with jax.default_matmul_precision("highest"):
        want = R._layer(x[0], whole, fields, 11)
        h = R.mixer(x[0], whole, fields, 11)                # every rank alike
        r2 = S.norm(h[None], whole["post_attn_norm"], cfg)
        routed_want, shared_want = R.moe(r2[0], whole, fields)
        total = jnp.zeros_like(routed_want)
        for rank in range(4):
            share_fields = {**fields, "num_local_experts": 3,
                            "expert_offset": 3 * rank}
            share_cfg = T.TransformerConfig(**share_fields,
                                            dtype=jnp.float32, remat=False)
            lw = {**whole, **{k: whole[k][3 * rank:3 * rank + 3]
                              for k in ("we_gate", "we_up", "we_down")}}
            m, counts = M.expert_mlp(r2, lw, cfg=share_cfg)
            routed_ref, shared_ref = R.moe(r2[0], lw, share_fields)
            np.testing.assert_allclose(m[0], routed_ref + shared_ref,
                                       atol=2e-5)
            np.testing.assert_allclose(shared_ref, shared_want, atol=1e-6)
            total = total + (m[0] - shared_ref)
            assert int(counts[0]) == 11 * 5
            # and the program's own layer is its share of the reference's
            y, _ = S.mlp(h[None], lw, cfg=share_cfg)
            np.testing.assert_allclose(
                y[0], R._layer(x[0], lw, share_fields, 11), atol=1e-4)
    np.testing.assert_allclose(total, routed_want, atol=1e-4)
    np.testing.assert_allclose(
        h + fields["residual_multiplier"] * (total + shared_want), want,
        atol=1e-4)
    assert float(jnp.max(jnp.abs(routed_want))) > 1e-2


def test_the_router_is_top_k_then_softmax(model):
    """``mla_moe.route``'s softmax over the whole width renormalised over
    the chosen IS the softmax over the chosen logits."""
    _, cfg, params = model
    lw = params["layers"][0]
    rows = jax.random.normal(jax.random.key(6), (9, 64))
    w_held, idx = M.route(rows, lw["w_router"], cfg)
    top, want_idx = jax.lax.top_k(rows @ lw["w_router"], 3)
    w = jax.nn.softmax(top, axis=-1)
    assert np.array_equal(np.sort(idx, -1), np.sort(want_idx, -1))
    want = sum(jnp.where(want_idx[:, j, None] == jnp.arange(4, 8),
                         w[:, j, None], 0.0) for j in range(3))
    np.testing.assert_allclose(w_held, want, atol=1e-6)
    assert S.ROUTER_SCORING == "softmax"


# ------------------------------------------------- pool, engine, counters

def test_the_pool_holds_slots_as_the_mixer_shapes_them(model):
    _, cfg, _ = model
    pool = PagedKVPool(cfg, 9, 8, n_slots=3)
    assert len(pool.bufs.k) == len(pool.bufs.v) == 1 == paged_layers(cfg)
    assert len(pool.bufs.state) == len(pool.bufs.conv) == 3
    assert pool.bufs.state[0].shape == (3, 16, 128)
    assert pool.bufs.state[0].dtype == jnp.float32
    assert pool.bufs.conv[0].shape == (3, 3, 160)
    assert not slab_pool(cfg) and pool_shape(cfg, 9, 8) == (9, 8, 2, 16)
    assert row_layout(cfg) == ((2, 16), True)
    assert slot_state_bytes(cfg) == 3 * (8 * 16 * 16 * 4 + 3 * 160 * 4)
    assert pool.state_bytes == 3 * slot_state_bytes(cfg)
    with pytest.raises(ValueError, match="n_slots >= 1"):
        PagedKVPool(cfg, 9, 8)


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernels"])
def test_the_engine_serves_the_reference_and_counts(model, kernel):
    fields, cfg, params = model
    eng = ServingEngine(params, cfg, max_batch=3, page_size=8, max_seq_len=64,
                        prefill_chunk=16, paged_kernel=kernel)
    rng = np.random.default_rng(0)
    for n in (19, 40, 5, 16, 23):
        eng.submit(rng.integers(1, 256, n).astype(np.int32), 6)
    done = eng.run()
    s = eng.stats
    assert len(done) == 5 and all(len(r.tokens) == 6 for r in done)
    for r in done:
        seq = np.concatenate([r.prompt, np.asarray(r.tokens[:-1], np.int32)])
        pos = len(r.prompt) - 1 + np.arange(6)
        z = R.logits_at(params, jnp.asarray(seq), jnp.asarray(pos), fields,
                        block=len(seq))
        assert r.tokens == [int(t) for t in np.asarray(jnp.argmax(z, -1))]
    assert s["state_resets"] == s["admitted"] == 5
    assert s["lin_scan_rows"] == 19 + 40 + 5 + 16 + 23
    assert s["moe_expert_layer_steps"] == 4 * s["decode_steps"]
    assert s["moe_assignments"] == 3 * 4 * s["state_slot_steps"] > 0
    assert 0 < s["moe_experts_touched"] <= s["moe_assignments_held"]
    on = s["decode_steps"] if kernel else 0
    assert s["lin_step_inplace_steps"] == s["decode_inplace_steps"] == on
    assert eng.lin_step_kernel == kernel
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
                       match=f"Mamba-2 \\+ attention block.*"
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
                       match="Mamba-2 \\+ attention block.*not built"):
        call()


@pytest.mark.parametrize("over,match", [
    ({"mamba_n_heads": 0}, r"needs \['mamba_n_heads'\]"),
    ({"shared_intermediate_size": 0}, r"needs \['shared_intermediate_size'\]"),
    ({"num_local_experts": 0}, r"needs \['num_local_experts'\]"),
    ({"layer_types": ("mamba", "attention")}, "layer_types must name each"),
    ({"layer_types": ("mamba", "mamba", "full", "mamba")},
     "layer_types must name each"),
    ({"mamba_d_head": 8}, "is not mamba_expand x hidden_size"),
    ({"mamba_d_conv": 1}, "mamba_d_conv must be >= 2"),
    ({"router_width": 6}, "held experts 4..7 are not among the router's 6"),
    ({"num_experts_per_tok": 13}, "num_experts_per_tok exceeds router_width"),
    ({"mamba_n_groups": 2}, "mamba_n_groups=1 only"),
    ({"mamba_chunk_size": 128}, "mamba_chunk_size=256 only"),
    ({"tie_word_embeddings": False}, "tie_word_embeddings=True only"),
    ({"num_experts": 4}, "num_experts=0 only"),
    ({"n_experts": 4}, "n_experts=0 only"),
    ({"routed_scaling_factor": 2.5}, "routed_scaling_factor=1.0 only"),
])
def test_a_variant_the_block_does_not_build_is_refused_by_name(over, match):
    with pytest.raises(ValueError, match=match):
        T.TransformerConfig(**{**FIELDS, **over})


# ------------------------------------- for a TPU, at the published widths

def test_both_programs_lower_for_tpu_at_published_widths(monkeypatch):
    """The engine's decode and prefill programs at the published widths
    (three layers: m a m; 16 slots), lowered FOR a TPU on this host: every
    Mamba-2 layer's decode step is one Mosaic call on the state as stored,
    in place, the attention layer one paged kernel call at the scale 1/128
    over the 4-D pool as it is, the experts one grouped call a layer;
    nothing gathers the view; the prefill scan stays XLA at blocks of
    256."""
    from distributed_training_sandbox_tpu.ops.flash_prefill import (
        prefill_kernel_takes)
    from distributed_training_sandbox_tpu.ops.paged_attention import (
        decode_kernel_takes)
    monkeypatch.setattr(S, "SCAN_BLOCK", 256)
    B, page, P, chunk = 16, 16, 320, 512
    cfg = T.TransformerConfig(
        vocab_size=100352, hidden_size=4096, intermediate_size=768,
        num_hidden_layers=3, num_attention_heads=32, num_key_value_heads=8,
        rms_norm_eps=1e-5, tie_word_embeddings=True, nope_interval=0,
        layer_types=("mamba", "attention", "mamba"), mamba_n_heads=128,
        mamba_d_head=64, mamba_d_state=128, mamba_d_conv=4,
        num_local_experts=18, router_width=72, num_experts_per_tok=10,
        shared_intermediate_size=1536, embedding_multiplier=12,
        residual_multiplier=0.22, attention_multiplier=0.0078125,
        logits_scaling=16, dtype=jnp.bfloat16, remat=False)
    assert decode_kernel_takes(cfg.dtype, 128, page)
    assert prefill_kernel_takes(cfg.dtype, 128, page, chunk)
    assert step_kernel_takes(*S.state_shape(cfg))
    assert S.slot_state_bytes(cfg) == 4_194_304 + 3 * 8_448 * 2
    sd = jax.ShapeDtypeStruct
    params = jax.eval_shape(lambda: T.init_params(jax.random.key(0), cfg))
    bufs = jax.eval_shape(
        lambda: PagedKVPool(cfg, B * P + 1, page, n_slots=B).bufs)
    assert bufs.state[0].shape == (B, 128, 8192) and len(bufs.k) == 1
    assert bufs.k[0].shape == (B * P + 1, 16, 8, 128)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    lower = lambda step, args: step.trace(*args).lower(  # noqa: E731
        lowering_platforms=("tpu",)).as_text()
    text = lower(
        E.make_serve_decode_step(cfg, paged_kernel=True),
        (bufs, params, sd((B, P), jnp.int32), sd((B,), jnp.int32),
         sd((B,), jnp.int32), sd((B,), jnp.int32), sd((B,), jnp.bool_),
         sd((5,), jnp.int32)))
    assert "_decode_float" in text
    assert f"tensor<{B}x{P * page}x8x128" not in text      # no gathered view
    assert text.count("call @_step(") == 2 \
        == S.layer_kinds(cfg).count("linear")
    assert text.count('kernel_name = "_step_kernel"') == 1
    assert f"tensor<{B}x128x2xf32>" in text                # B | C columns
    assert text.count("_visit_kernel") >= 1                # grouped experts
    text = lower(
        E.make_serve_prefill_step(cfg, paged_kernel=True),
        (bufs, params, sd((1, P), jnp.int32), sd((1, chunk), jnp.int32),
         sd((), jnp.int32), sd((), jnp.int32), sd((), jnp.int32)))
    assert "_prefill_float" in text and "_step_kernel" not in text
    assert f"tensor<1x{P * page}x8x128" not in text
    assert "tensor<2x1x128x256x256xf32>" in text           # a head's decays
    assert "tensor<2x1x256x256xf32>" in text               # C B^T, shared
