"""Composable N-D mesh driver: one rule-driven train step per `MeshPlan`.

ROADMAP item 1, second half.  PR 17 made the partition rules
(``analysis.rules.RuleSet``) the declarative source of truth — placement,
generated contracts, drift lint — but execution still lived in one
hand-built vertical driver per strategy.  This module folds execution
onto the same rules:

  * :class:`MeshPlan` names the mesh — axis sizes over dp/fsdp/tp/sp —
    plus the weight-update-sharding degree W0–W3 of arXiv:2004.13336
    ("Automatic Cross-Replica Sharding of Weight Update Computation"),
    which collapses ddp and the three ZeRO stages into ONE config axis
    instead of four modules.  ``w_layout`` picks the W3 representation:
    ``"flat"`` = ZeRO-3 per-param owner chunks, ``"named"`` = FSDP named
    leaf dims (same memory law, different wire choreography).
  * :func:`make_composable_train_step` executes any supported plan.
    Legacy-shaped plans (1-D data parallel at any W degree, dp×tp,
    dp×sp, fsdp) dispatch to the existing hand factories with identical
    hyperparameters — the parity law holds BITWISE, loss-for-loss,
    because it is the same compiled program.  Genuinely new shapes
    (dp×fsdp×tp) run the rule-driven 3-axis step below, whose
    param/opt/batch shardings come from the strategy's ``RuleSet`` and
    whose ``CollectiveContract`` is *generated* by
    ``analysis.contract_gen`` — nothing hand-registered.

The 3-axis dp×fsdp×tp choreography (``_make_dp_fsdp_tp_step``):
FSDP gathers over ``fsdp`` around each scanned layer (backward
re-gathers via remat; grads arrive pre-summed over fsdp through the
all_gather's psum_scatter transpose), Megatron tp math inside the layer
body (two rejoin psums per layer over ``tp``), batch sharded jointly
over ``(dp, fsdp)``, and one fused grad psum over the axes each leaf is
replicated on, normalized by the total device count — the same
transpose algebra the 1-D/2-D steps pin in isolation, composed.
"""

from __future__ import annotations

import dataclasses
import functools
import re
from typing import Any, Callable

import jax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import transformer as T
from ..ops import collectives as C
from ..utils.profiling import scope
from . import fsdp, optim, sequence, tensor, zero
from .ddp import make_ddp_train_step

MESH_PLAN_AXES = ("dp", "fsdp", "tp", "sp")
W_LAYOUTS = ("flat", "named")

_PLAN_TOKEN = re.compile(r"^(dp|fsdp|tp|sp)(\d+)$|^w([0-3])(flat|named)?$")


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    """Named mesh-axis sizes + the weight-update-sharding degree.

    Grammar (``MeshPlan.parse``): ``x``- or ``,``-separated tokens,
    each ``<axis><size>`` or ``w<degree>[flat|named]``, e.g.
    ``"dp8xw1"`` (ZeRO-1), ``"dp2xfsdp2xtp2"`` (the 3-axis combo),
    ``"dp8xw3named"`` (FSDP).  Omitted axes default to 1; omitted W
    degree to 0 (replicated update = ddp).

    The W degree applies to the ``dp`` axis (that is what
    arXiv:2004.13336 shards the weight update over); a ``fsdp`` axis of
    size > 1 is *named-dim W3 over its own axis* and therefore requires
    ``w == 0`` on dp — the two compose as separate mesh axes, not as one
    doubly-sharded axis.
    """
    dp: int = 1
    fsdp: int = 1
    tp: int = 1
    sp: int = 1
    w: int = 0
    w_layout: str = "flat"

    def __post_init__(self):
        for name in MESH_PLAN_AXES:
            size = getattr(self, name)
            if not isinstance(size, int) or size < 1:
                raise ValueError(f"MeshPlan.{name}={size!r}: axis sizes "
                                 f"must be integers >= 1")
        if self.w not in (0, 1, 2, 3):
            raise ValueError(f"MeshPlan.w={self.w!r}: the weight-update-"
                             f"sharding degree is W0..W3")
        if self.w_layout not in W_LAYOUTS:
            raise ValueError(f"MeshPlan.w_layout={self.w_layout!r}: "
                             f"choose from {W_LAYOUTS}")
        if self.w and self.fsdp > 1:
            raise ValueError(
                f"MeshPlan(dp={self.dp}, fsdp={self.fsdp}, w={self.w}): "
                f"an fsdp axis IS named-dim W3 over its own axis; a "
                f"nonzero W degree on dp does not compose with it")
        if self.w_layout == "named" and self.w not in (0, 3):
            raise ValueError(
                f"MeshPlan.w_layout='named' is the FSDP representation "
                f"of W3; it is meaningless at w={self.w} (zero{self.w} "
                f"state is flat owner chunks by construction)")

    # ------------------------------------------------------------ grammar

    @classmethod
    def parse(cls, text: str) -> "MeshPlan":
        """``"dp2xfsdp2xtp2"`` / ``"dp8,w1"`` / ``"dp8xw3named"`` -> plan."""
        sizes = {}
        w, w_layout = 0, None
        for tok in re.split(r"[x,×]", text.strip().lower()):
            if not tok:
                continue
            m = _PLAN_TOKEN.match(tok)
            if not m:
                raise ValueError(
                    f"bad MeshPlan token {tok!r} in {text!r}; tokens are "
                    f"<axis><size> (axes {MESH_PLAN_AXES}) or "
                    f"w<0-3>[flat|named]")
            if m.group(1):
                if m.group(1) in sizes:
                    raise ValueError(f"duplicate axis {m.group(1)!r} "
                                     f"in {text!r}")
                sizes[m.group(1)] = int(m.group(2))
            else:
                w = int(m.group(3))
                w_layout = m.group(4)
        return cls(w=w, w_layout=w_layout or "flat", **sizes)

    def describe(self) -> str:
        toks = [f"{a}{getattr(self, a)}" for a in MESH_PLAN_AXES
                if getattr(self, a) > 1] or ["dp1"]
        if self.w:
            toks.append(f"w{self.w}"
                        + ("named" if self.w == 3
                           and self.w_layout == "named" else ""))
        return "x".join(toks)

    # ----------------------------------------------------------- geometry

    @property
    def ways(self) -> int:
        """Total device count the plan spans."""
        return self.dp * self.fsdp * self.tp * self.sp

    def axis_sizes(self) -> dict:
        return {a: getattr(self, a) for a in MESH_PLAN_AXES}

    def mesh_axes(self) -> dict:
        """Axis-name -> size for ``make_mesh``: the size-1 axes are
        dropped (a trivial axis only renames specs), dp kept as the
        fallback so the mesh is never empty."""
        active = {a: getattr(self, a) for a in MESH_PLAN_AXES
                  if getattr(self, a) > 1}
        return active or {"dp": 1}

    # Memory-law factors for the analytic waterline
    # (``memory_plan.predictor.analytic_waterline``): how many ways the
    # params at rest / optimizer state / global batch divide.
    @property
    def param_shard_ways(self) -> int:
        return self.fsdp * self.tp * (self.dp if self.w >= 3 else 1)

    @property
    def opt_shard_ways(self) -> int:
        return self.fsdp * self.tp * (self.dp if self.w >= 1 else 1)

    @property
    def data_ways(self) -> int:
        """Ways the global batch dim divides (sp divides seq, not batch)."""
        return self.dp * self.fsdp

    # --------------------------------------------------------- resolution

    def normalized(self) -> "MeshPlan":
        """Canonical form: a pure ``fsdp`` axis with nothing else active
        IS legacy FSDP — named-dim W3 over an axis called ``dp`` — so it
        renames to keep the legacy mesh/contract/ruleset names."""
        if self.fsdp > 1 and self.dp == 1 and self.tp == 1 \
                and self.sp == 1 and self.w == 0:
            return MeshPlan(dp=self.fsdp, w=3, w_layout="named")
        return self

    def strategy_name(self) -> str:
        """The registered strategy (= RuleSet = contract) name this plan
        executes as.  Raises for unsupported axis combinations."""
        p = self.normalized()
        if p.fsdp > 1:
            if p.tp > 1 and p.sp == 1:
                return "composable_dp_fsdp_tp"
            raise ValueError(
                f"MeshPlan {self.describe()!r}: unsupported axis combo — "
                f"an fsdp axis currently composes with tp only "
                f"(dp×fsdp×tp); dp×fsdp alone or ×sp is future work")
        if p.tp > 1:
            if p.sp > 1:
                raise ValueError(
                    f"MeshPlan {self.describe()!r}: dp×tp×sp runs through "
                    f"the hand tp driver (make_tp_train_step sp_axis=); "
                    f"it is not yet folded into the composable surface")
            if p.w:
                raise ValueError(f"MeshPlan {self.describe()!r}: W>0 on "
                                 f"dp does not compose with tp yet")
            return "tp"
        if p.sp > 1:
            if p.w not in (0, 3):
                raise ValueError(f"MeshPlan {self.describe()!r}: sp rides "
                                 f"fsdp-over-dp (W3 named); w={p.w} does "
                                 f"not apply")
            return "sp"
        # 1-D data parallel: the W degree picks the strategy.
        if p.w == 0:
            return "ddp"
        if p.w == 1:
            return "composable_zero1"
        if p.w == 2:
            return "zero2"
        return "fsdp" if p.w_layout == "named" else "zero3"

    def validate(self, n_devices: int | None = None,
                 model_cfg: T.TransformerConfig | None = None,
                 seq_len: int | None = None) -> None:
        """Feasibility rules (the tuner prunes on the same three):
        axis product == device count, tp divides the head counts,
        sp divides the sequence length."""
        if n_devices is not None and self.ways != n_devices:
            raise ValueError(
                f"MeshPlan {self.describe()!r} spans {self.ways} devices; "
                f"{n_devices} available (axis product must match exactly)")
        if model_cfg is not None and self.tp > 1:
            tensor.check_tp_divisibility(model_cfg, self.tp)
        if seq_len is not None and self.sp > 1 and seq_len % self.sp:
            raise ValueError(f"MeshPlan sp={self.sp} must divide the "
                             f"sequence length {seq_len}")


def plan_feasible(dp: int, fsdp: int, tp: int, sp: int, *,
                  n_devices: int, n_heads: int | None = None,
                  n_kv_heads: int | None = None,
                  seq_len: int | None = None) -> bool:
    """Boolean twin of :meth:`MeshPlan.validate` over raw ints — the
    tuner's enumeration-time filter, importable without jax/model
    machinery (``tuner.knobs`` mirrors this logic; pinned together by
    tests/test_composable.py)."""
    if dp * fsdp * tp * sp != n_devices:
        return False
    if tp > 1:
        for heads in (n_heads, n_kv_heads):
            if heads is not None and heads % tp:
                return False
    if sp > 1 and seq_len is not None and seq_len % sp:
        return False
    return True


# -------------------------------------------------------------- the build

@dataclasses.dataclass
class ComposableBuild:
    """Everything a driver needs to run one plan: the jitted step, the
    placed initial state, the batch spec, and the contract/ruleset
    identity the telemetry verdicts key on."""
    plan: MeshPlan               # normalized
    strategy: str                # RuleSet / contract name
    mesh: Mesh
    step: Callable
    params: Any                  # placed as the step's in_spec expects
    opt_state: Any
    batch_spec: P
    contract_kwargs: dict = dataclasses.field(default_factory=dict)


def _spec_tree_axes(spec: P) -> set:
    out = set()
    for entry in spec:
        if entry is None:
            continue
        out.update((entry,) if isinstance(entry, str) else entry)
    return out


def _ruleset(strategy: str):
    from ..analysis import rules as R
    return R.RULESETS[strategy]


def _batch_spec_from_rules(strategy: str) -> P:
    """The strategy's batch placement straight from its RuleSet (every
    registered batch rule set here is a single catch-all rule)."""
    from ..analysis.rules import to_partition_spec
    rs = _ruleset(strategy)
    return to_partition_spec(rs.batch_rules[0].spec)


def shard_params_by_rules(params, mesh: Mesh, strategy: str,
                          role: str = "params"):
    """Place a (host/replicated) tree at its at-rest sharding as the
    strategy's partition rules declare it — the rule-driven twin of the
    per-family ``shard_params_*`` helpers."""
    specs = _ruleset(strategy).partition_specs(params, role)
    return jax.tree.map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)),
        params, specs, is_leaf=lambda x: isinstance(x, P))


def _mlp_chunk_loss(params, axis: str):
    """Auto-build the ZeRO-3 chunked loss for the toy-MLP tree (a list
    of ``{"w", "b"}`` layers — the `_zero_driver` model family)."""
    if not (isinstance(params, (list, tuple)) and params
            and all(isinstance(layer, dict) and set(layer) == {"w", "b"}
                    for layer in params)):
        raise ValueError(
            "MeshPlan w=3 w_layout='flat' (zero3) auto-builds its chunked "
            "loss for the toy-MLP tree only (list of {'w','b'} layers); "
            "pass a transformer plan w_layout='named' instead, or use "
            "zero.make_zero3_train_step directly with a custom chunk loss")
    shapes = [{k: v.shape for k, v in layer.items()} for layer in params]
    return zero.make_zero3_mlp_loss(shapes, axis)


def make_composable_train_step(
    params,
    plan: MeshPlan,
    mesh: Mesh,
    *,
    model_cfg: T.TransformerConfig | None = None,
    loss_fn: Callable | None = None,
    rebuild: str = "broadcast",
    overlap: str = "none",
    accum_steps: int = 1,
    donate: bool = True,
) -> ComposableBuild:
    """Resolve a :class:`MeshPlan` to one executable build.

    ``params`` enter replicated/host-side; the build places them at
    their at-rest sharding itself (flat chunks, named dims, tp shards —
    whatever the plan's rules say).  ``model_cfg`` is required for
    transformer-family plans (any of fsdp-named/tp/sp active);
    ``loss_fn`` is required for the replicated-param data-parallel
    family (ddp/zero1/zero2) and optional elsewhere.

    Legacy-shaped plans run the HAND step factories with their own
    default hyperparameters — bitwise-identical to the bespoke drivers
    by construction (pinned by tests/test_composable.py).  The dp×fsdp×tp
    combo runs the rule-driven 3-axis step (new code, new generated
    contract).
    """
    T.require_dense_block(model_cfg,
                          "parallel.composable.make_composable_train_step")
    p = plan.normalized()
    strategy = p.strategy_name()
    # the mesh must realize the plan exactly (axis names AND sizes)
    want = (p.mesh_axes() if strategy != "composable_dp_fsdp_tp"
            else {a: getattr(p, a) for a in ("dp", "fsdp", "tp")})
    got = {k: int(v) for k, v in mesh.shape.items()}
    if got != {k: int(v) for k, v in want.items()}:
        raise ValueError(f"mesh axes {got} do not realize MeshPlan "
                         f"{p.describe()!r} (want {want})")
    batch_spec = _batch_spec_from_rules(strategy)

    if strategy == "composable_dp_fsdp_tp":
        if model_cfg is None:
            raise ValueError("dp×fsdp×tp is a transformer plan; pass "
                             "model_cfg")
        shards = shard_params_by_rules(params, mesh, strategy)
        step = _make_dp_fsdp_tp_step(
            shards, model_cfg, mesh, strategy=strategy, overlap=overlap,
            accum_steps=accum_steps, donate=donate, loss_fn=loss_fn)
        opt_state = fsdp.init_fsdp_opt_state(shards)
        return ComposableBuild(p, strategy, mesh, step, shards, opt_state,
                               batch_spec,
                               {"n_layers": model_cfg.num_hidden_layers})

    if strategy == "tp":
        if model_cfg is None:
            raise ValueError("a tp plan needs model_cfg")
        shards = tensor.shard_params_tp(params, mesh)
        step = tensor.make_tp_train_step(
            shards, model_cfg, mesh, overlap=overlap,
            accum_steps=accum_steps, donate=donate, loss_fn=loss_fn)
        opt_state = fsdp.init_fsdp_opt_state(shards)
        return ComposableBuild(p, strategy, mesh, step, shards, opt_state,
                               batch_spec,
                               {"n_layers": model_cfg.num_hidden_layers})

    if strategy == "sp":
        if model_cfg is None:
            raise ValueError("an sp plan needs model_cfg")
        shards = fsdp.shard_params_fsdp(params, mesh, "dp")
        step = sequence.make_sp_train_step(
            shards, model_cfg, mesh, accum_steps=accum_steps,
            donate=donate, loss_fn=loss_fn)
        opt_state = fsdp.init_fsdp_opt_state(shards)
        return ComposableBuild(p, strategy, mesh, step, shards, opt_state,
                               batch_spec,
                               {"n_layers": model_cfg.num_hidden_layers})

    if strategy == "fsdp":
        if model_cfg is None:
            raise ValueError("a w3-named (fsdp) plan needs model_cfg")
        shards = fsdp.shard_params_fsdp(params, mesh, "dp")
        step = fsdp.make_fsdp_train_step(
            shards, model_cfg, mesh, overlap=overlap,
            accum_steps=accum_steps, donate=donate, loss_fn=loss_fn)
        opt_state = fsdp.init_fsdp_opt_state(shards)
        return ComposableBuild(p, strategy, mesh, step, shards, opt_state,
                               batch_spec,
                               {"n_layers": model_cfg.num_hidden_layers})

    # -------- 1-D data-parallel family: the W degree is the strategy ----
    if strategy == "zero3":
        chunk_loss = _mlp_chunk_loss(params, "dp") if loss_fn is None \
            else loss_fn
        opt_state = zero.init_zero_opt_state(params, mesh, "dp")
        step = zero.make_zero3_train_step(chunk_loss, mesh, "dp",
                                          donate=donate)
        chunks = zero.shard_params_zero3(params, mesh, "dp")
        return ComposableBuild(p, strategy, mesh, step, chunks, opt_state,
                               batch_spec)

    if loss_fn is None:
        raise ValueError(f"a replicated-param data-parallel plan "
                         f"({strategy}) needs loss_fn")
    if strategy in ("composable_zero1", "zero2"):
        stage = 1 if strategy == "composable_zero1" else 2
        step = zero.make_zero_train_step(loss_fn, mesh, "dp", stage=stage,
                                         rebuild=rebuild, donate=donate)
        opt_state = zero.init_zero_opt_state(params, mesh, "dp")
        return ComposableBuild(p, strategy, mesh, step, params, opt_state,
                               batch_spec, {"rebuild": rebuild})

    assert strategy == "ddp", strategy
    step = make_ddp_train_step(
        loss_fn, lambda g, s, p_: optim.adam_update(g, s, p_), mesh, "dp",
        donate=donate)
    opt_state = optim.adam_init(params)
    return ComposableBuild(p, strategy, mesh, step, params, opt_state,
                           batch_spec)


# ------------------------------------------------- the new 3-axis step

def _make_dp_fsdp_tp_step(
    shards,
    cfg: T.TransformerConfig,
    mesh: Mesh,
    *,
    strategy: str = "composable_dp_fsdp_tp",
    dp_axis: str = "dp",
    fsdp_axis: str = "fsdp",
    tp_axis: str = "tp",
    overlap: str = "none",
    accum_steps: int = 1,
    lr: float = 3e-4,
    b1: float = 0.9,
    b2: float = 0.95,
    eps: float = 1e-8,
    donate: bool = True,
    loss_fn: Callable | None = None,
):
    """Jitted dp×fsdp×tp step:
    ``(param_shards, opt_state, batch) -> (param_shards, opt_state, loss)``.

    Placement comes from the strategy's RuleSet (column-parallel
    projections ``(L, in⊘fsdp, out⊘tp)``, row-parallel ``(L, in⊘tp,
    out⊘fsdp)``, everything else fsdp-sharded as in named-dim W3) and
    the choreography composes the pinned 1-D mechanisms:

      * per-layer fsdp all_gathers inside the remat scan (backward
        re-gathers; the gather transpose psum_scatters grads over fsdp),
      * Megatron tp layer math via the ``layer_body`` seam (two rejoin
        psums per layer over tp — each gathered projection is full on
        its fsdp dim, still a local tp shard),
      * batch sharded jointly over ``(dp, fsdp)`` — both axes carry
        data; the grad sync psums over dp (+ tp where a leaf is
        tp-replicated) and normalizes by dp·fsdp·tp, the fsdp sum
        having already arrived through the gather transpose.
    """
    tensor.check_tp_divisibility(cfg, int(mesh.shape[tp_axis]))
    if overlap not in ("none", "ring"):
        raise ValueError(f"overlap={overlap!r}: the 3-axis step composes "
                         f"'none' or 'ring' tp rejoins")
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    specs = _ruleset(strategy).partition_specs(shards, "params")
    fsdp.check_divisibility(shards, specs, mesh)
    layer_specs = specs["layers"]
    # inside the scan body each stacked leaf loses its layer dim
    hook_specs = jax.tree.map(lambda s: P(*s[1:]), layer_specs,  # spec-ok
                              is_leaf=lambda x: isinstance(x, P))
    ws_dp = int(mesh.shape[dp_axis])
    ws_fsdp = int(mesh.shape[fsdp_axis])
    ws_tp = int(mesh.shape[tp_axis])
    n_total = ws_dp * ws_fsdp * ws_tp

    base_loss = loss_fn or T.lm_loss
    layer_body = functools.partial(T._layer_body, tp_axis=tp_axis,
                                   tp_overlap=overlap)

    def layer_hook(layer):
        with scope("fsdp_layer_gather"):
            return jax.tree.map(
                lambda x, s: fsdp._gather_leaf(x, s, fsdp_axis),
                layer, hook_specs, is_leaf=lambda x: isinstance(x, P))

    def sharded_loss(shards_, batch):
        with scope("fsdp_root_gather"):
            outer = {k: fsdp._gather_leaf(v, specs[k], fsdp_axis)
                     for k, v in shards_.items() if k != "layers"}
        params = {**outer, "layers": shards_["layers"]}
        return base_loss(params, batch, cfg, layer_hook=layer_hook,
                         layer_body=layer_body)

    def sync_grad(g, spec):
        # fsdp contributions were summed by the gather transposes; psum
        # the dp replicas (+ tp for tp-replicated leaves — tp-sharded
        # leaves already carry the rejoin-psum transpose's ws_tp factor),
        # then normalize once by the full device count.
        axes = (dp_axis,) + ((tp_axis,)
                             if tp_axis not in _spec_tree_axes(spec)
                             else ())
        return lax.psum(g, axes) / n_total

    def step(shards_, opt_state, batch):
        with scope("forward_backward"):
            loss, grad_shards = fsdp.microbatch_value_and_grad(
                sharded_loss, shards_, batch, accum_steps)
        with scope("loss_mean"):
            loss = lax.pmean(loss, (dp_axis, fsdp_axis, tp_axis))
        with scope("grad_sync"):
            grad_shards = jax.tree.map(
                sync_grad, grad_shards, specs,
                is_leaf=lambda x: isinstance(x, P))
        with scope("opt_step"):
            shards_, opt_state = optim.adam_update(
                grad_shards, opt_state, shards_,
                lr=lr, b1=b1, b2=b2, eps=eps)
        return shards_, opt_state, loss

    state_specs = optim.AdamState(mu=specs, nu=specs, count=P())
    batch_spec = P((dp_axis, fsdp_axis))  # spec-ok
    sharded = C.smap(step, mesh,
                     in_specs=(specs, state_specs, batch_spec),
                     out_specs=(specs, state_specs, P()))
    return jax.jit(sharded, donate_argnums=(0, 1) if donate else ())
