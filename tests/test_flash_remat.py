"""The splash kernel's forward runs once a step: under
``attention_impl="flash"`` every remat policy keeps the kernel's own
residuals (its output and its log-sum-exp, ``T.FLASH_RESIDUALS``), so the
layer's recomputation in the backward scan does not re-run it.  And its
backward is one kernel a layer (the library's fused form, which makes
dq beside dk and dv): the rule that sizes its KV block from the window,
the calls counted in the jaxpr, and its gradients against the plain
attention's in the library's interpret mode.

CPU only, at the jaxpr / StableHLO level: tracing a ``pallas_call`` and
lowering it for the TPU platform need no chip; nothing here is a time."""

import collections
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals
from jax._src.config import traceback_in_locations_limit
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_training_sandbox_tpu.models import transformer as T
from distributed_training_sandbox_tpu.parallel import fsdp
from distributed_training_sandbox_tpu.utils import make_mesh

S, L, NQ, HD = 256, 2, 2, 128
FLASH = T.TransformerConfig(
    vocab_size=256, hidden_size=256, intermediate_size=256,
    num_hidden_layers=L, num_attention_heads=NQ, num_key_value_heads=1,
    head_dim=HD, nope_interval=2, loss_vocab_chunk=64, dtype=jnp.bfloat16,
    attention_impl="flash")
POLICIES = ("full", "save_attn", "save_dots", "save_dots_q8")
IDS = jnp.zeros((1, S), jnp.int32)


def _parent_policy(cfg):
    """``resolve_remat_policy`` as it was before the kernel's residuals
    were kept (no offload: no test here sets it)."""
    return {
        "save_attn": jax.checkpoint_policies.save_only_these_names("attn_out"),
        "save_dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        "save_dots_q8": jax.checkpoint_policies.save_only_these_names("dot_q8"),
        "full": None,
    }[cfg.remat_policy]


@pytest.fixture
def as_parent(monkeypatch):
    """The program as the parent built it: the old policy mapping, and a
    splash kernel made without a ``residual_checkpoint_name``."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk)
    make = sk.make_splash_mha_single_device

    def make_unnamed(*a, residual_checkpoint_name=None, **kw):
        return make(*a, **kw)

    monkeypatch.setattr(T, "resolve_remat_policy", _parent_policy)
    monkeypatch.setattr(sk, "make_splash_mha_single_device", make_unnamed)


@pytest.fixture
def unfused_backward(monkeypatch):
    """The kernel as it was built before the backward was fused: a dq
    kernel of its own beside dkv, both at the forward's blocks."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk)
    blocks = sk.BlockSizes

    def unfused(*, use_fused_bwd_kernel, block_q, block_kv, **kw):
        assert use_fused_bwd_kernel
        kw.update(block_q_dkv=block_q, block_kv_dkv=block_kv,
                  block_kv_dkv_compute=kw["block_kv_compute"])
        return blocks(block_q=block_q, block_kv=block_kv,
                      block_q_dq=block_q, block_kv_dq=block_kv, **kw)

    monkeypatch.setattr(sk, "BlockSizes", unfused)


def _param_shapes(cfg):
    return jax.eval_shape(lambda k: T.init_params(k, cfg), jax.random.key(0))


def _grad_jaxpr(cfg):
    return jax.make_jaxpr(jax.grad(
        lambda p: T.lm_loss(p, (IDS, IDS), cfg)))(_param_shapes(cfg))


def _subjaxprs(eqn):
    for v in eqn.params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            inner = getattr(x, "jaxpr", x)
            if hasattr(inner, "eqns"):
                yield inner


def _pallas_eqns(jaxpr):
    """The Pallas calls in ``jaxpr``, nested ones included."""
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            yield e
        for j in _subjaxprs(e):
            yield from _pallas_eqns(j)


def _kernels(jaxpr) -> list[str]:
    return [e.params["name"] for e in _pallas_eqns(jaxpr)]


def _kernel_scans(jaxpr) -> list[tuple[list[str], list]]:
    """(kernel names, stacked outputs as (shape, dtype)) of every scan
    that holds a Pallas call, in program order: the forward layer scan,
    then the backward one."""
    out = []
    for e in jaxpr.eqns:
        if e.primitive.name == "scan":
            names = _kernels(e.params["jaxpr"].jaxpr)
            if names:
                stacked = e.outvars[e.params["num_carry"]:]
                out.append((names, [(v.aval.shape, str(v.aval.dtype))
                                    for v in stacked]))
        else:
            for j in _subjaxprs(e):
                out += _kernel_scans(j)
    return out


@pytest.mark.parametrize("policy", POLICIES)
def test_flash_forward_runs_once_under_every_policy(policy):
    """Forward scan: the forward kernel, once, with its two residuals
    among the scan's stacked outputs at (n_q, S, hd) bf16 and (n_q, S)
    float32 a layer; backward scan: ONE kernel, the fused dkv that makes
    dq too, and no forward."""
    cfg = dataclasses.replace(FLASH, remat=True, remat_policy=policy)
    (fwd, stacked), (bwd, _) = _kernel_scans(_grad_jaxpr(cfg).jaxpr)
    assert fwd == ["splash_mha_fwd_residuals"]
    assert bwd == ["splash_mha_dkv_no_residuals"]
    assert ((L, 1, NQ, S, HD), "bfloat16") in stacked
    assert ((L, 1, NQ, S), "float32") in stacked


@pytest.mark.parametrize("policy", POLICIES)
def test_the_parent_ran_the_forward_in_both_scans(policy, as_parent):
    """What the change removed, and that the fixture is the parent."""
    cfg = dataclasses.replace(FLASH, remat=True, remat_policy=policy)
    (fwd, stacked), (bwd, _) = _kernel_scans(_grad_jaxpr(cfg).jaxpr)
    assert fwd == ["splash_mha_fwd_residuals"]
    assert sorted(bwd) == ["splash_mha_dkv_no_residuals",
                           "splash_mha_fwd_residuals"]
    assert ((L, 1, NQ, S), "float32") not in stacked


def _layer_residuals(cfg) -> list[tuple[tuple, str]]:
    """What ``saved_residuals`` lists for one rematerialised layer besides
    the layer's arguments and constants, as (shape, dtype)."""
    cos, sin = T._rope_tables(S, cfg.resolved_head_dim, cfg.rope_theta)
    layer = jax.tree.map(lambda x: jnp.zeros(x.shape[1:], x.dtype),
                         _param_shapes(cfg)["layers"])
    body = jax.checkpoint(
        lambda x, lyr: T._layer_body(x, lyr, cfg=cfg, cos=cos, sin=sin,
                                     use_rope=True)[0],
        prevent_cse=False, policy=T.resolve_remat_policy(cfg))
    x = jnp.zeros((1, S, cfg.hidden_size), cfg.dtype)
    return sorted((a.shape, str(a.dtype))
                  for a, why in saved_residuals(body, x, layer)
                  if "argument" not in why and "constant" not in why)


def test_full_saves_the_kernels_two_tensors_and_nothing_else():
    """``saved_residuals`` of one rematerialised layer under "full": the
    kernel's log-sum-exp and output, at (n_q, S) float32 and
    (n_q, S, hd) bf16."""
    cfg = dataclasses.replace(FLASH, remat=True, remat_policy="full")
    assert _layer_residuals(cfg) == [((1, NQ, S), "float32"),
                                     ((1, NQ, S, HD), "bfloat16")]


def test_the_parents_full_saved_nothing(as_parent):
    cfg = dataclasses.replace(FLASH, remat=True, remat_policy="full")
    assert _layer_residuals(cfg) == []


def _unnumbered(module: str) -> str:
    """StableHLO text without the numeric suffixes jax gives repeated
    function names (``closed_call_91``): they count every function
    traced so far, so one function more or fewer renames the rest."""
    return re.sub(r"@([A-Za-z_]\w*?)_\d+\b", r"@\1", module)


def _tpu_text(traced) -> str:
    """A traced program lowered for the TPU platform, as text that does
    not depend on who called: a Mosaic kernel's payload carries its
    tracebacks, so they are cut to nothing while it is lowered."""
    with traceback_in_locations_limit(0):
        return _unnumbered(
            traced.lower(lowering_platforms=("tpu",)).as_text())


def _lower_grad_for_tpu(cfg) -> str:
    f = jax.jit(jax.grad(lambda p: T.lm_loss(p, (IDS, IDS), cfg)))
    return _tpu_text(f.trace(_param_shapes(cfg)))


def test_without_remat_the_program_is_the_parents(monkeypatch, as_parent):
    """``remat=False``: nothing is rematerialised, so a name on the
    residuals changes nothing that is lowered."""
    cfg = dataclasses.replace(FLASH, remat=False)
    parent = _lower_grad_for_tpu(cfg)
    monkeypatch.undo()
    assert parent.count("splash_mha_fwd") == 1
    assert _lower_grad_for_tpu(cfg) == parent


@pytest.mark.parametrize("policy", POLICIES)
def test_other_attention_keeps_the_policy_it_had(policy, monkeypatch):
    """``attention_impl="xla"``: the gradient's jaxpr is the parent's
    under every policy (``"ring"`` takes the same branch: the rule reads
    ``attention_impl == "flash"`` and nothing else)."""
    cfg = dataclasses.replace(FLASH, attention_impl="xla", remat=True,
                              remat_policy=policy)
    text = lambda: re.sub(  # noqa: E731  (a policy prints its address)
        r" at 0x[0-9a-f]+", "", str(_grad_jaxpr(cfg)))
    ours = text()
    assert T.FLASH_RESIDUALS not in ours
    monkeypatch.setattr(T, "resolve_remat_policy", _parent_policy)
    assert text() == ours


@pytest.mark.parametrize("impl,policy,kept", [
    ("ring", "full", False), ("ring", "save_attn", False),
    ("xla", "full", False), ("xla", "save_dots", False),
    ("flash", "full", True), ("flash", "save_attn", True),
    ("flash", "save_dots", True), ("flash", "save_dots_q8", True)])
def test_policy_saves_the_residuals_name_only_for_flash(impl, policy, kept):
    """The policy itself, asked about a ``name`` equation as
    ``jax.checkpoint`` asks it: the residuals' name is saveable exactly
    when the attention is the kernel; what a policy saved before, it
    still saves."""
    from jax._src.ad_checkpoint import name_p
    cfg = dataclasses.replace(FLASH, attention_impl=impl, remat=True,
                              remat_policy=policy,
                              sp_axis="sp" if impl == "ring" else None)
    pol = T.resolve_remat_policy(cfg)
    saves = lambda name: bool(  # noqa: E731
        pol is not None and pol(name_p, name=name))
    assert saves(T.FLASH_RESIDUALS) is kept
    assert saves("attn_out") is (policy == "save_attn")
    assert saves("dot_q8") is (policy == "save_dots_q8")
    if pol is None:
        assert policy == "full" and impl != "flash"


@pytest.mark.parametrize("impl", ["flash", "xla"])
def test_offload_parks_the_named_saves_and_keeps_the_residuals(
        impl, monkeypatch):
    """On a backend with a host space (steered here, no new option) the
    offload variant sends the policy's own names to the host and keeps
    the kernel's residuals on the device."""
    from jax._src.ad_checkpoint import name_p
    from jax._src.interpreters import partial_eval as pe
    from distributed_training_sandbox_tpu.memory_plan import offload
    monkeypatch.setattr(offload, "supports_host_offload", lambda: True)
    cfg = dataclasses.replace(FLASH, attention_impl=impl, remat=True,
                              remat_policy="save_attn",
                              offload_activations=True)
    pol = T.resolve_remat_policy(cfg)
    assert isinstance(pol(name_p, name="attn_out"), pe.Offloadable)
    kept = pol(name_p, name=T.FLASH_RESIDUALS)
    assert kept is (pe.Saveable if impl == "flash" else pe.Recompute)


# ------------------------------------------------- the FSDP step, for a TPU

def _fsdp_step_and_arguments():
    mesh = make_mesh({"dp": 2}, devices=jax.devices()[:2], register=False)
    params = T.init_params(jax.random.key(0), FLASH)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                             fsdp.fsdp_specs(params),
                             is_leaf=lambda x: isinstance(x, P))
    shards = jax.device_put(params, shardings)
    step = fsdp.make_fsdp_train_step(shards, FLASH, mesh)
    batch = jax.device_put((jnp.zeros((2, S), jnp.int32),) * 2,
                           NamedSharding(mesh, P("dp")))
    return step, (shards, fsdp.init_fsdp_opt_state(shards), batch)


def _lower_fsdp_step_for_tpu() -> str:
    step, arguments = _fsdp_step_and_arguments()
    return _tpu_text(step.trace(*arguments))


def _functions(module: str) -> collections.Counter:
    """The module's functions as a multiset of (name, body)."""
    out, cur = collections.Counter(), None
    for line in module.split("\n"):
        m = re.match(r"  func\.func (?:public|private) @([\w.]+)", line)
        if m:
            cur = (m.group(1), [])
        if cur:
            cur[1].append(line)
            if line == "  }":
                out[(cur[0], "\n".join(cur[1]))] += 1
                cur = None
    return out


def test_fsdp_step_differs_from_the_parents_only_in_the_layer_scans(
        monkeypatch, as_parent):
    """PR 27's guard, turned round: lowered for the TPU platform, the
    explicit-FSDP step (remat "full", the cells' policy) differs from the
    parent's in ``main`` (whose two layer scans stack and read the kept
    residuals), in the two layer bodies and in the kernel's forward
    wrapper; the backward body lost its forward call; the loss head's
    block and every helper are the parent's text."""
    parent = _lower_fsdp_step_for_tpu()
    monkeypatch.undo()
    change = _lower_fsdp_step_for_tpu()
    for kernel, (was, now) in {"splash_mha_fwd": (2, 1),
                               "splash_mha_dq": (0, 0),
                               "splash_mha_dkv": (1, 1)}.items():
        assert (parent.count(kernel), change.count(kernel)) == (was, now)
    was, now = _functions(parent), _functions(change)
    assert sum(was.values()) == sum(now.values()) + 1 > 10
    gone, came = was - now, now - was
    is_layer = lambda body: "@_splash_attention" in body  # noqa: E731
    for name, body in list(gone) + list(came):
        assert name == "main" or name == "_splash_attention" or (
            name == "closed_call" and is_layer(body)), name
    count = lambda fs, name: sum(  # noqa: E731
        n for (k, _), n in fs.items() if k == name)
    assert (count(gone, "main"), count(came, "main")) == (1, 1)
    assert (count(gone, "closed_call"), count(came, "closed_call")) == (2, 2)
    # the forward wrapper now returns the log-sum-exp too; the backward
    # body's own forward call is gone with its wrapper
    assert (count(gone, "_splash_attention"),
            count(came, "_splash_attention")) == (2, 1)
    shared = [k for k, _ in was & now]
    assert shared.count("closed_call") == 1      # the loss head's one block


# ------------------------------------------- the backward is one kernel

@pytest.mark.parametrize("policy", POLICIES)
def test_the_parent_ran_two_backward_kernels(policy, unfused_backward):
    """What the fused backward removed, and that the fixture is the
    parent: a dq kernel beside dkv in the backward scan."""
    cfg = dataclasses.replace(FLASH, remat=True, remat_policy=policy)
    (fwd, _), (bwd, _) = _kernel_scans(_grad_jaxpr(cfg).jaxpr)
    assert fwd == ["splash_mha_fwd_residuals"]
    assert sorted(bwd) == ["splash_mha_dkv_no_residuals",
                           "splash_mha_dq_no_residuals"]


def _fsdp_step_kernels() -> list[list[str]]:
    """The Pallas calls of the explicit-FSDP step's layer scans, forward
    scan first, from the step's jaxpr (the step does not run)."""
    step, arguments = _fsdp_step_and_arguments()
    return [names for names, _ in
            _kernel_scans(step.trace(*arguments).jaxpr.jaxpr)]


def test_fsdp_step_backward_holds_one_attention_kernel():
    assert _fsdp_step_kernels() == [["splash_mha_fwd_residuals"],
                                    ["splash_mha_dkv_no_residuals"]]


def test_the_parents_fsdp_step_backward_held_two(unfused_backward):
    fwd, bwd = _fsdp_step_kernels()
    assert fwd == ["splash_mha_fwd_residuals"]
    assert sorted(bwd) == ["splash_mha_dkv_no_residuals",
                           "splash_mha_dq_no_residuals"]


@pytest.mark.parametrize("seq", [256, 2048, 8192, 32768])
def test_backward_kv_block_follows_the_window(seq):
    """The rule by itself, and the kernel the library builds from it, by
    shape only: the KV block divides the window, its compute block is a
    multiple of 128 that divides it, and the fused kernel writes at most
    8 dq partials, each of q's shape and dtype."""
    bq, bkv, bkv_c = T.flash_backward_blocks(seq)
    assert seq % bkv == 0 and seq % bq == 0
    assert bkv_c % 128 == 0 and bkv % bkv_c == 0
    assert seq // bkv <= 8 and bkv >= min(seq, 1024)
    q = jax.ShapeDtypeStruct((1, seq, NQ, HD), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, seq, 1, HD), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: T._attention_flash(q, k, v, 1.0).sum().astype(
            jnp.float32), argnums=(0, 1, 2)))(q, kv, kv)
    fwd, bwd = _pallas_eqns(jaxpr.jaxpr)
    assert bwd.params["name"] == "splash_mha_dkv_no_residuals"
    # (a batch of one is squeezed away by the call's batching rule)
    shapes = [(v.aval.shape[-4:], str(v.aval.dtype)) for v in bwd.outvars]
    assert ((seq // bkv, NQ, seq, HD), "bfloat16") in shapes


@pytest.mark.parametrize("seq,want", [(128, 128), (640, 640), (1152, 1152),
                                      (3072, 1024), (12288, 1536),
                                      (16384, 2048), (65536, 4096)])
def test_backward_kv_block_at_any_lane_aligned_window(seq, want):
    """Any multiple of 128: the smallest lane-aligned divisor of the
    window that is at least an eighth of it, between 1024 and 4096."""
    bq, bkv, bkv_c = T.flash_backward_blocks(seq)
    assert bkv == want and bq == min(1024, seq)
    assert bkv % bkv_c == 0 and bkv_c % 128 == 0


@pytest.fixture
def interpreted(monkeypatch):
    """The library's kernels in its ``interpret=True`` mode, and a KV
    block of half the window for the backward, so that there ARE two dq
    partials to sum at a size the CPU runs."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk)
    make = sk.make_splash_mha_single_device
    monkeypatch.setattr(
        sk, "make_splash_mha_single_device",
        lambda *a, **kw: make(*a, interpret=True, **kw))
    monkeypatch.setattr(T, "flash_backward_blocks",
                        lambda seq: (128, seq // 2, 128))


@pytest.mark.kernels
@pytest.mark.parametrize("batch,hd", [(1, 128), (2, 64)])
def test_fused_backward_matches_plain_attention(batch, hd, interpreted):
    """dq, dk, dv of ``_attention_flash`` against ``_attention_xla``'s in
    float32, GQA with 4 query heads on 2 KV heads over 512 positions;
    the second case has a batch, which the kernel is vmapped over."""
    seq, nq, nkv = 512, 4, 2
    ks = jax.random.split(jax.random.key(batch), 4)
    q = jax.random.normal(ks[0], (batch, seq, nq, hd), jnp.float32)
    k, v = (jax.random.normal(x, (batch, seq, nkv, hd), jnp.float32)
            for x in ks[1:3])
    w = jax.random.normal(ks[3], (batch, seq, nq, hd), jnp.float32)
    scale = hd ** -0.5

    def grads(attend):
        return jax.grad(lambda *a: jnp.sum(attend(*a, scale) * w),
                        argnums=(0, 1, 2))(q, k, v)

    jaxpr = jax.make_jaxpr(lambda: grads(T._attention_flash))()
    _, bwd = _pallas_eqns(jaxpr.jaxpr)
    assert (2, nq, seq, hd) in [v.aval.shape[-4:] for v in bwd.outvars]
    for got, want in zip(grads(T._attention_flash), grads(T._attention_xla)):
        want = np.asarray(want)
        np.testing.assert_allclose(np.asarray(got), want, rtol=0,
                                   atol=2e-6 * np.abs(want).max())
