"""Tiny CPU-mesh builds of every strategy's train step.

One place that knows how to construct a minimal, fast instance of each
strategy exactly the way its ``scripts/`` driver does — shared by the
contract pytest suite and ``scripts/lint_sharding.py`` so "lower the
step and check the choreography" is a one-liner everywhere.

Strategies self-register through :func:`register_strategy`: each builder
function is decorated with the names it knows how to construct, and
``STRATEGIES`` / :func:`build_strategy` are derived from the registry —
adding a strategy is one decorated function, not three parallel edits.
``scripts/lint_sharding.py`` cross-checks the registry against
``contracts.CONTRACTS`` so a builder registered without a collective
contract (or a contract with no builder) fails CI instead of silently
escaping the analyzer.

Everything here is CPU-sized: toy-MLP widths of ~100 and the TINY_LM
transformer at sequence length 32, so the full registry lowers, lints
and runs 3 steps in well under a minute on the 8-device simulated mesh.
"""

from __future__ import annotations

import dataclasses as _dc
from dataclasses import dataclass, field
from typing import Any, Callable

from .contracts import CONTRACTS, ContractContext

# the canonical bucket size for the ddp_bucketed fixture — small enough
# that the toy MLP needs several buckets, so the formula is exercised
FIXTURE_BUCKET_MB = 0.05

# name -> builder; insertion order IS the canonical strategy order
_BUILDERS: dict[str, Callable[..., "StrategyBuild"]] = {}


def register_strategy(*names: str):
    """Register a fixture builder under one or more strategy names.

    The builder is called as ``fn(name, mesh=, scale=, seq=,
    batch_size=)`` and must return a :class:`StrategyBuild`.  Duplicate
    registration is a hard error — two builders claiming one name is a
    merge accident, not a feature."""
    if not names:
        raise ValueError("register_strategy needs at least one name")

    def deco(fn):
        for n in names:
            if n in _BUILDERS:
                raise ValueError(
                    f"strategy {n!r} already registered by "
                    f"{_BUILDERS[n].__name__}")
            _BUILDERS[n] = fn
        return fn
    return deco


def registered_strategies() -> tuple[str, ...]:
    """Every registered strategy name, in registration order."""
    return tuple(_BUILDERS)


def contract_coverage() -> tuple[list[str], list[str]]:
    """Registry ↔ contract cross-check for the lint gate.

    Returns ``(missing_contract, unregistered_contract)``: strategies
    with a fixture builder but no ``CONTRACTS`` entry (an analyzer
    blind spot — error), and contracts with no registered builder (dead
    contract — warning)."""
    regs = registered_strategies()
    missing = [s for s in regs if s not in CONTRACTS]
    orphans = [s for s in CONTRACTS if s not in regs]
    return missing, orphans


@dataclass
class StrategyBuild:
    """A lowered-and-runnable strategy instance plus everything the
    analyzers need to judge it."""
    strategy: str
    step: Callable                    # jitted step fn
    args: tuple                       # example invocation args
    advance: Callable | None          # (args, outputs) -> next args
    mesh: Any                         # jax Mesh or None (pipeline)
    ctx: ContractContext
    donate: bool
    full_param_shapes: set = field(default_factory=set)

    @property
    def contract(self):
        return CONTRACTS[self.strategy]


def _state_advance(args, out):
    """(params, opt, batch) step contract: feed state back, reuse batch."""
    params, opt, loss = out
    return (params, opt, args[2])


@register_strategy("ddp", "ddp_bucketed", "ddp_q8", "zero1", "zero2",
                   "zero3")
def _build_mlp_dp(strategy: str, *, mesh=None, scale: int = 100,
                  seq: int = 32, batch_size: int = 8) -> StrategyBuild:
    """Toy-MLP strategies over a 1-D dp mesh."""
    import jax

    from ..models import zero_toy_mlp
    from ..models.mlp import mse_loss
    from ..parallel import optim
    from ..parallel import make_ddp_train_step
    from ..parallel.zero import (
        make_zero_train_step, init_zero_opt_state, make_zero3_train_step,
        make_zero3_mlp_loss, shard_params_zero3)
    from ..utils import make_mesh, set_seed
    from .hlo_lint import param_shapes

    key = set_seed(0)
    mesh = mesh or make_mesh(register=False)
    params = zero_toy_mlp(key, scale=scale)
    width = 10_000 // scale
    kx, ky = jax.random.split(key)
    b = (jax.random.normal(kx, (batch_size, width)),
         jax.random.normal(ky, (batch_size, width)))
    shapes = param_shapes(params, min_numel=256)
    extra = {"bucket_mb": FIXTURE_BUCKET_MB} \
        if strategy in ("ddp_bucketed", "ddp_q8") else {}
    ctx = ContractContext.capture(params=params, mesh=mesh,
                                  n_layers=len(params), **extra)
    if strategy in ("ddp", "ddp_bucketed", "ddp_q8"):
        step = make_ddp_train_step(
            mse_loss,
            lambda g, s, p: optim.sgd_update(g, s, p, lr=1e-3),
            mesh, "dp",
            bucket_mb=FIXTURE_BUCKET_MB
            if strategy in ("ddp_bucketed", "ddp_q8") else None,
            quantize_grads=strategy == "ddp_q8")
        args = (params, optim.sgd_init(params), b)
    elif strategy in ("zero1", "zero2"):
        step = make_zero_train_step(mse_loss, mesh, "dp",
                                    stage=int(strategy[-1]))
        args = (params, init_zero_opt_state(params, mesh, "dp"), b)
    else:
        layer_shapes = [{k: v.shape for k, v in layer.items()}
                        for layer in params]
        step = make_zero3_train_step(
            make_zero3_mlp_loss(layer_shapes, "dp"), mesh, "dp")
        args = (shard_params_zero3(params, mesh, "dp"),
                init_zero_opt_state(params, mesh, "dp"), b)
    return StrategyBuild(strategy, step, args, _state_advance, mesh,
                         ctx, donate=True, full_param_shapes=shapes)


@register_strategy("fsdp", "fsdp_ring", "fsdp_fp8",
                   "fsdp_ring_fused_pallas", "fsdp_offload", "tp",
                   "tp_ring", "tp_q8", "sp", "moe")
def _build_transformer(strategy: str, *, mesh=None, scale: int = 100,
                       seq: int = 32,
                       batch_size: int = 8) -> StrategyBuild:
    """TINY_LM transformer strategies over 1-D dp or dp × {tp,sp,ep}."""
    import jax
    import jax.numpy as jnp

    from ..models import transformer as T
    from ..parallel import fsdp, sequence, tensor, expert
    from ..utils import make_mesh, set_seed
    from .hlo_lint import param_shapes

    key = set_seed(0)
    n_dev = len(jax.devices())
    mcfg = T.TINY_LM
    second_axis = {"fsdp": None, "fsdp_ring": None, "fsdp_fp8": None,
                   "fsdp_ring_fused_pallas": None,
                   "fsdp_offload": None, "tp": "tp",
                   "tp_ring": "tp", "tp_q8": "tp", "sp": "sp",
                   "moe": "ep"}[strategy]
    if mesh is None:
        if second_axis is None:
            mesh = make_mesh(register=False)
        else:
            if n_dev < 4:
                raise RuntimeError(
                    f"{strategy} fixture needs >= 4 devices "
                    f"(have {n_dev})")
            mesh = make_mesh({"dp": n_dev // 2, second_axis: 2},
                             register=False)
    if strategy == "moe":
        mcfg = _dc.replace(mcfg, n_experts=4,
                           moe_ffn=max(mcfg.intermediate_size // 4, 8))
    params = T.init_params(key, mcfg)
    shapes = param_shapes(params, min_numel=1024)
    ctx = ContractContext.capture(params=params, mesh=mesh,
                                  n_layers=mcfg.num_hidden_layers)
    if strategy in ("fsdp", "fsdp_ring", "fsdp_fp8",
                    "fsdp_ring_fused_pallas"):
        if strategy == "fsdp_fp8":
            # the fp8 precision leg: e4m3 fwd / e5m2 bwd scaled matmuls
            # in the dense seam — same gather choreography as fsdp
            mcfg = _dc.replace(mcfg, matmul_precision="fp8")
        overlap = {"fsdp_ring": "ring",
                   "fsdp_ring_fused_pallas": "ring_fused_pallas"}.get(
                       strategy, "none")
        shards = fsdp.shard_params_fsdp(params, mesh)
        step = fsdp.make_fsdp_train_step(shards, mcfg, mesh,
                                         overlap=overlap)
    elif strategy == "fsdp_offload":
        # host-offloaded optimizer state: park the Adam moments in
        # pinned host memory (identity placement on the CPU sim) and
        # declare the resulting transfer counts into the contract ctx
        from ..memory_plan import offload_tree, plan_offload
        shards = fsdp.shard_params_fsdp(params, mesh)
        opt0 = fsdp.init_fsdp_opt_state(shards)
        oplan = plan_offload("opt", opt0)
        if oplan.supported:
            opt0 = offload_tree(opt0)
        step = fsdp.make_fsdp_train_step(shards, mcfg, mesh,
                                         offload="opt")
        ctx = ContractContext.capture(
            params=params, mesh=mesh,
            n_layers=mcfg.num_hidden_layers,
            offload=oplan.to_dict())
        probe = (jnp.zeros((batch_size, seq), jnp.int32),) * 2
        return StrategyBuild(strategy, step, (shards, opt0, probe),
                             _state_advance, mesh, ctx, donate=True,
                             full_param_shapes=shapes)
    elif strategy == "sp":
        shards = fsdp.shard_params_fsdp(params, mesh, "dp")
        step = sequence.make_sp_train_step(shards, mcfg, mesh)
    elif strategy in ("tp", "tp_ring", "tp_q8"):
        shards = tensor.shard_params_tp(params, mesh)
        step = tensor.make_tp_train_step(
            shards, mcfg, mesh,
            overlap={"tp_ring": "ring", "tp_q8": "q8"}.get(
                strategy, "none"))
    else:
        shards = expert.shard_moe_lm_params(params, mesh)
        step = expert.make_moe_lm_train_step(shards, mcfg, mesh)
    opt = fsdp.init_fsdp_opt_state(shards)
    probe = (jnp.zeros((batch_size, seq), jnp.int32),) * 2
    return StrategyBuild(strategy, step, (shards, opt, probe),
                         _state_advance, mesh, ctx, donate=True,
                         full_param_shapes=shapes)


@register_strategy("composable_zero1", "composable_dp_fsdp_tp")
def _build_composable(strategy: str, *, mesh=None, scale: int = 100,
                      seq: int = 32,
                      batch_size: int = 8) -> StrategyBuild:
    """MeshPlan-driven builds through ``make_composable_train_step`` —
    the generated-contract strategies.  ``composable_zero1`` is the toy
    MLP at W1 over flat dp (zero1's bitwise twin through the composable
    surface); ``composable_dp_fsdp_tp`` is TINY_LM on the 3-axis
    dp×fsdp×tp mesh, placement from its RuleSet."""
    import jax
    import jax.numpy as jnp

    from ..models import transformer as T, zero_toy_mlp
    from ..models.mlp import mse_loss
    from ..parallel.composable import MeshPlan, make_composable_train_step
    from ..utils import make_mesh, set_seed
    from .hlo_lint import param_shapes

    key = set_seed(0)
    n_dev = len(jax.devices())
    if strategy == "composable_zero1":
        mesh = mesh or make_mesh(register=False)
        params = zero_toy_mlp(key, scale=scale)
        plan = MeshPlan(dp=int(mesh.shape["dp"]), w=1)
        build = make_composable_train_step(params, plan, mesh,
                                           loss_fn=mse_loss)
        width = 10_000 // scale
        kx, ky = jax.random.split(key)
        b = (jax.random.normal(kx, (batch_size, width)),
             jax.random.normal(ky, (batch_size, width)))
        shapes = param_shapes(params, min_numel=256)
        ctx = ContractContext.capture(params=params, mesh=mesh,
                                      n_layers=len(params),
                                      **build.contract_kwargs)
    else:
        if mesh is None:
            if n_dev < 8:
                raise RuntimeError(
                    f"{strategy} fixture needs >= 8 devices "
                    f"(have {n_dev})")
            mesh = make_mesh({"dp": n_dev // 4, "fsdp": 2, "tp": 2},
                             register=False)
        mcfg = T.TINY_LM
        params = T.init_params(key, mcfg)
        plan = MeshPlan(dp=int(mesh.shape["dp"]),
                        fsdp=int(mesh.shape["fsdp"]),
                        tp=int(mesh.shape["tp"]))
        build = make_composable_train_step(params, plan, mesh,
                                           model_cfg=mcfg)
        b = (jnp.zeros((batch_size, seq), jnp.int32),) * 2
        shapes = param_shapes(params, min_numel=1024)
        ctx = ContractContext.capture(params=params, mesh=mesh,
                                      **build.contract_kwargs)
    return StrategyBuild(strategy, build.step,
                         (build.params, build.opt_state, b),
                         _state_advance, mesh, ctx, donate=True,
                         full_param_shapes=shapes)


@register_strategy("serve_decode", "serve_decode_paged_kernel")
def _build_serve_decode(strategy: str, *, mesh=None, scale: int = 100,
                        seq: int = 32,
                        batch_size: int = 8) -> StrategyBuild:
    """Serving decode step over dp × tp (``_paged_kernel``: attention
    through the Pallas paged decode kernel, bitwise choreography twin)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..models import transformer as T
    from ..models.generate import _decode_cfg
    from ..parallel import tensor
    from ..serving import PagedKVPool, make_serve_decode_step
    from ..serving.engine import _dense_serving_tree
    from ..utils import make_mesh, set_seed
    from .hlo_lint import param_shapes

    key = set_seed(0)
    n_dev = len(jax.devices())
    mcfg = T.TINY_LM
    if mesh is None:
        if n_dev < 4:
            raise RuntimeError(
                f"serve_decode fixture needs >= 4 devices "
                f"(have {n_dev})")
        mesh = make_mesh({"dp": n_dev // 2, "tp": 2}, register=False)
    params = T.init_params(key, mcfg)
    shapes = param_shapes(params, min_numel=1024)
    ctx = ContractContext.capture(params=params, mesh=mesh,
                                  n_layers=mcfg.num_hidden_layers)
    # the tree an engine's programs read: the fused q, k, v leaves too
    shards = _dense_serving_tree(tensor.shard_params_tp(params, mesh),
                                 _decode_cfg(mcfg), mesh, "tp")
    page_size, pages_per = 8, 4
    pool = PagedKVPool(_decode_cfg(mcfg),
                       batch_size * pages_per + 1, page_size,
                       mesh=mesh)
    step = make_serve_decode_step(
        mcfg, shards, mesh=mesh, pool_spec=pool.spec,
        paged_kernel=strategy == "serve_decode_paged_kernel")
    pages = jnp.asarray(np.arange(
        1, batch_size * pages_per + 1,
        dtype=np.int32).reshape(batch_size, pages_per))
    args = (pool.bufs, shards, pages,
            jnp.zeros((batch_size,), jnp.int32),       # tokens
            jnp.zeros((batch_size,), jnp.int32),       # lengths
            jnp.full((batch_size,), page_size * pages_per - 1,
                     jnp.int32),                       # stop_at
            jnp.ones((batch_size,), bool))             # active
    # outputs: (nxt, new_len, new_active, bufs, occ) — feed the
    # donated pool and the token/length/active chain back in
    advance = lambda args, out: (out[3], args[1], args[2], out[0],
                                 out[1], args[5], out[2])
    return StrategyBuild(strategy, step, args, advance, mesh, ctx,
                         donate=True, full_param_shapes=shapes)


@register_strategy("serve_decode_spec", "serve_prefill_flash")
def _build_serve_frontier(strategy: str, *, mesh=None, scale: int = 100,
                          seq: int = 32,
                          batch_size: int = 8) -> StrategyBuild:
    """The PR-18 serving steps over dp × tp: the speculative (B, k+1)
    verify forward and the batched flash-kernel prefill chunk.  Both
    share serve_decode's wire choreography — 2 rejoin psums per
    unrolled layer over tp, nothing else."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..models import transformer as T
    from ..models.generate import _decode_cfg
    from ..parallel import tensor
    from ..serving import (PagedKVPool, make_serve_prefill_batch_step,
                           make_serve_spec_verify_step)
    from ..serving.engine import _dense_serving_tree
    from ..utils import make_mesh, set_seed
    from .hlo_lint import param_shapes

    key = set_seed(0)
    n_dev = len(jax.devices())
    mcfg = T.TINY_LM
    if mesh is None:
        if n_dev < 4:
            raise RuntimeError(
                f"{strategy} fixture needs >= 4 devices "
                f"(have {n_dev})")
        mesh = make_mesh({"dp": n_dev // 2, "tp": 2}, register=False)
    params = T.init_params(key, mcfg)
    shapes = param_shapes(params, min_numel=1024)
    ctx = ContractContext.capture(params=params, mesh=mesh,
                                  n_layers=mcfg.num_hidden_layers)
    # the tree an engine's programs read: the fused q, k, v leaves too
    shards = _dense_serving_tree(tensor.shard_params_tp(params, mesh),
                                 _decode_cfg(mcfg), mesh, "tp")
    page_size, pages_per = 8, 4
    pool = PagedKVPool(_decode_cfg(mcfg),
                       batch_size * pages_per + 1, page_size,
                       mesh=mesh)
    pages = jnp.asarray(np.arange(
        1, batch_size * pages_per + 1,
        dtype=np.int32).reshape(batch_size, pages_per))
    if strategy == "serve_decode_spec":
        k = 3
        step = make_serve_spec_verify_step(
            mcfg, shards, mesh=mesh, pool_spec=pool.spec)
        args = (pool.bufs, shards, pages,
                jnp.zeros((batch_size, k + 1), jnp.int32),  # proposals
                jnp.zeros((batch_size,), jnp.int32),        # lengths
                jnp.full((batch_size,), page_size * pages_per - 1,
                         jnp.int32),                        # stop_at
                jnp.ones((batch_size,), bool))              # active
        # outputs: (greedy, bufs, occ) — the host accept/rollback jit
        # sits between bursts, so the fixture just recycles the pool
        advance = lambda args, out: (out[1],) + args[1:]
    else:
        chunk = 16
        step = make_serve_prefill_batch_step(
            mcfg, shards, mesh=mesh, pool_spec=pool.spec,
            flash_prefill=True)
        ids = jnp.asarray(np.random.default_rng(0).integers(
            1, mcfg.vocab_size, size=(batch_size, chunk),
            dtype=np.int32))
        args = (pool.bufs, shards, pages, ids,
                jnp.zeros((batch_size,), jnp.int32),        # chunk pos
                jnp.full((batch_size,), chunk, jnp.int32))  # prompt len
        # outputs: (first_tok, bufs)
        advance = lambda args, out: (out[1],) + args[1:]
    return StrategyBuild(strategy, step, args, advance, mesh, ctx,
                         donate=True, full_param_shapes=shapes)


@register_strategy("gpipe", "1f1b")
def _build_pipeline(strategy: str, *, mesh=None, scale: int = 100,
                    seq: int = 32,
                    batch_size: int = 8) -> StrategyBuild:
    """Pipeline schedules: single-device stage programs."""
    import jax

    from ..models import pp_toy_mlp
    from ..models.mlp import PP_TOY_SIZES
    from ..parallel.pipeline import build_pipeline
    from ..utils import set_seed

    key = set_seed(0)
    params = pp_toy_mlp(key)
    stages = build_pipeline(params, 2)
    x = jax.random.normal(key, (batch_size, PP_TOY_SIZES[0]))
    ctx = ContractContext.capture(params=stages[0].params,
                                  n_layers=len(params))
    return StrategyBuild(strategy, stages[0].fwd,
                         (stages[0].params, x),
                         None, None, ctx, donate=False)


# the public, ordered tuple every caller keys on — derived from the
# registry so it can never drift from what build_strategy dispatches
STRATEGIES = registered_strategies()


def build_strategy(strategy: str, *, mesh=None, scale: int = 100,
                   seq: int = 32, batch_size: int = 8) -> StrategyBuild:
    """Construct the named strategy's step the way its script does.

    Dispatches to the :func:`register_strategy`-decorated builder.
    ``mesh`` defaults to a fresh mesh of the canonical shape for that
    strategy over all visible devices (1-D ``dp``, or ``{dp: n/2, x: 2}``
    for the 2-D strategies)."""
    try:
        builder = _BUILDERS[strategy]
    except KeyError:
        raise KeyError(
            f"unknown strategy {strategy!r}; have {STRATEGIES}") from None
    return builder(strategy, mesh=mesh, scale=scale, seq=seq,
                   batch_size=batch_size)
