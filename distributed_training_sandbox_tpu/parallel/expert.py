"""Expert parallelism: switch-style MoE with ``all_to_all`` dispatch.

The reference records MoE/EP only as a learning note on how expert
parallelism folds into the mesh (``README.md:13-14`` — SURVEY.md §2.2:
absent as code).  On TPU it is the canonical use of ``lax.all_to_all``
(the collective the reference's course stops short of): experts shard
across the ``ep`` mesh axis, every device routes its tokens, and two
all_to_alls per layer move token buckets to their experts' devices and
back.

Mechanics (Switch Transformer, top-1, fixed capacity):

  * router: logits = x @ w_router, expert = argmax, gate = softmax prob
    of the chosen expert
  * capacity C per expert bucket; tokens overflowing their bucket are
    dropped (output 0 for them — the standard switch trade)
  * dispatch/combine are one-hot einsums over a (tokens, E, C) tensor —
    static shapes, MXU-friendly, the idiom XLA pipelines well
  * device d owns experts [d·E/ep, (d+1)·E/ep): the first all_to_all
    regroups buckets by owning device, the second returns them
  * aux load-balance loss: E · Σ_e fraction_e · mean_prob_e (Switch
    eq. 4), averaged over the ep group

Shapes are per-device inside ``shard_map``; expert weights live ONLY on
their owner (ep-sharded pytree), router weights are replicated.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import collectives as C
from ..utils.profiling import scope
from . import optim


class MoEParams(NamedTuple):
    """Per-device pytree: router replicated, experts ep-sharded dim 0."""
    w_router: jax.Array   # (H, E)
    w_gate: jax.Array     # (E_local, H, F)
    w_up: jax.Array       # (E_local, H, F)
    w_down: jax.Array     # (E_local, F, H)


def init_moe_params(key, *, hidden: int, ffn: int, n_experts: int,
                    dtype=jnp.float32) -> MoEParams:
    """Full (unsharded) init — shard with ``shard_moe_params``."""
    kr, kg, ku, kd = jax.random.split(key, 4)
    s_in = hidden ** -0.5
    s_ff = ffn ** -0.5
    return MoEParams(
        w_router=(jax.random.normal(kr, (hidden, n_experts), dtype) * s_in),
        w_gate=(jax.random.normal(kg, (n_experts, hidden, ffn), dtype)
                * s_in),
        w_up=(jax.random.normal(ku, (n_experts, hidden, ffn), dtype)
              * s_in),
        w_down=(jax.random.normal(kd, (n_experts, ffn, hidden), dtype)
                * s_ff))


def moe_specs(axis: str = "ep") -> MoEParams:
    return MoEParams(w_router=P(), w_gate=P(axis), w_up=P(axis),
                     w_down=P(axis))


def shard_moe_params(params: MoEParams, mesh: Mesh,
                     axis: str = "ep") -> MoEParams:
    return jax.tree.map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)),
        params, moe_specs(axis), is_leaf=lambda x: isinstance(x, P))


def _resolve_group(n_tokens: int, group_size: int) -> int:
    """Largest divisor of ``n_tokens`` that is <= ``group_size`` — the
    grouped dispatch must tile the local chunk exactly, so an awkward
    token count (sharded seq, odd batch) shrinks the group rather than
    raising; G=1 is the (valid, capacity≈cf/E-per-token) floor."""
    g = min(group_size, n_tokens)
    while n_tokens % g:
        g -= 1
    return g


def _grouped_caps(n_tokens: int, group_size: int, capacity_factor: float,
                  n_experts: int) -> tuple[int, int, int]:
    """(G, NG, capg) of the grouped dispatch — THE one place its group
    and per-group-capacity rule lives."""
    G = _resolve_group(n_tokens, group_size)
    capg = int(-(-G * capacity_factor // n_experts))
    return G, n_tokens // G, capg


def _group_slot_positions(eg: jax.Array, n_experts: int):
    """Per-(group, expert) bucket position of each token: ``onehot``
    (NG, G, E) int32 and ``pos`` (NG, G, E), -1 off the token's expert —
    shared by the dispatch and its drop-rate report."""
    onehot = jax.nn.one_hot(eg, n_experts, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=1) * onehot - 1
    return onehot, pos


def grouped_drop_fraction(expert: jax.Array, n_experts: int,
                          group_size: int, capacity_factor: float):
    """Fraction of (token, assignment) pairs the grouped dispatch would
    drop — computed with the SAME helpers as ``moe_mlp``'s "grouped"
    branch, so reports (scripts/moe_quality_ab.py) cannot drift from the
    timed path's semantics.  ``expert``: (N,) top-1 assignments or
    (N, k) top-k (choice-major priority, capacity cf·k·G/E — exactly the
    dispatch's rule)."""
    if expert.ndim == 1:
        expert = expert[:, None]
    N, k = expert.shape
    G, NG, capg = _grouped_caps(N, group_size, capacity_factor * k,
                                n_experts)
    eg = expert.reshape(NG, G, k).transpose(0, 2, 1).reshape(NG, k * G)
    _, pos = _group_slot_positions(eg, n_experts)
    return jnp.mean((jnp.max(pos, axis=-1) >= capg).astype(jnp.float32))


def _route_topk(x2d, w_router, k: int):
    """(N, H) tokens → (gates (N, k), experts (N, k), probs (N, E)).
    k = 1 keeps the Switch convention (gate = raw top prob); k ≥ 2
    normalizes the gates over the chosen experts (GShard top-2)."""
    logits = (x2d @ w_router).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = lax.top_k(probs, k)
    if k > 1:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return gates, experts, probs


def router_z_loss(x2d, w_router):
    """ST-MoE router z-loss: mean over tokens of logsumexp(logits)² —
    pulls router logits toward zero so the softmax stays in its
    responsive range (a collapsed router rides saturated logits where
    the balance aux gradient vanishes).  Recomputes the (N, E) router
    matmul — negligible next to the expert MLPs — so callers need no
    logits plumbing."""
    logits = (x2d @ w_router).astype(jnp.float32)
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)


def moe_mlp(x, w_router, w_gate, w_up, w_down, *, axis: str | None = "ep",
            capacity_factor: float = 2.0, dispatch: str = "grouped",
            group_size: int = 128, top_k: int = 1,
            matmul_precision: str = "bf16", router_z_ratio: float = 0.0):
    """The switch-MoE MLP on local tokens ``x`` (B, S, H) →
    ``(y, aux_loss)``.  ``w_gate/w_up/w_down`` hold this device's
    ``E_local`` experts on dim 0; ``axis=None`` means no expert
    parallelism (all experts local, no collectives) — the form the
    MoE transformer uses on a 1-D mesh and the dense oracle of the
    EP choreography.

    ``dispatch``: how tokens reach their (E, C, H) buckets.
      * "grouped" (default): tokens are split into groups of
        ``group_size``; each group routes its tokens to per-group expert
        buckets with a small one-hot matmul (G × E·capg), and one regular
        leading-dim transpose rearranges (NG, E, capg, H) → (E, NG·capg,
        H).  This is the GShard/Switch TPU idiom: dispatch/combine are
        MXU einsums + a layout-regular transpose, so the hot path never
        runs an XLA gather/scatter — which on TPU are row-serialized
        (~0.2 µs/row: a (32k, 2048) permutation costs ~6.5 ms vs ~0.4 ms
        for the group one-hot matmuls; measured on v5e, r3).  Capacity is
        enforced PER GROUP (capg = ceil(cf·G/E)): bursty groups drop
        sooner than the global rule, the standard trade of this layout.
        When ``group_size`` does not divide the local token count the
        group shrinks to the largest divisor (``_resolve_group``) so any
        chunk shape trains.
      * "sort": stable-sort tokens by expert, scatter kept ones into
        their slots, gather back — O(N·H) data movement, but every row
        moves through the serialized gather path (~66 ms vs grouped's
        ~39 ms per layer fwd+bwd at N=32k cf=2.0 on v5e).
      * "einsum": the classic one-hot (N, E, C) dispatch/combine einsums
        over the WHOLE chunk (GShard with one group).  O(N·E·C·H)
        compute — the semantics oracle: "grouped" with group_size=N
        computes identical outputs/gradients (pinned by tests).

    ``top_k``: experts per token.  1 = Switch (gate = raw top prob);
    2+ = GShard-style top-k (gates normalized over the chosen experts,
    per-group capacity capg = ceil(cf·k·G/E) counted with FIRST choices
    ahead of second choices — bursty seconds drop first).  top_k > 1
    requires the "grouped" dispatch.
    """
    ep = C.axis_size(axis) if axis else 1
    B, S, H = x.shape
    N = B * S
    E = w_router.shape[1]
    E_local = w_gate.shape[0]
    if E_local * ep != E:
        raise ValueError(f"router knows {E} experts but ep={ep} devices "
                         f"hold {E_local} each")
    cap = int(-(-N * capacity_factor // E))
    x2d = x.reshape(N, H)
    if top_k > 1 and dispatch != "grouped":
        raise ValueError(f"top_k={top_k} requires dispatch='grouped' "
                         f"(got {dispatch!r})")

    with scope("moe_route"):
        gates, experts, probs = _route_topk(x2d, w_router, top_k)
        gate, expert = gates[:, 0], experts[:, 0]  # k=1 paths' view

    if dispatch == "grouped":
        G, NG, capg = _grouped_caps(N, group_size,
                                    capacity_factor * top_k, E)
        cap = NG * capg   # downstream a2a reshapes see one (E, cap, H)
        with scope("moe_dispatch"):
            # assignments flattened FIRST-choices-first within each
            # group: index j·G + t — earlier choices claim capacity
            # before any second choice does.
            eg = experts.reshape(NG, G, top_k).transpose(
                0, 2, 1).reshape(NG, top_k * G)
            onehot, pos = _group_slot_positions(eg, E)
            kept = (pos < capg) & (onehot > 0)
            slotoh = jax.nn.one_hot(jnp.clip(pos, 0, capg - 1), capg,
                                    dtype=jnp.bool_)
            disp = (kept[..., None] & slotoh).reshape(
                NG, top_k, G, E * capg).astype(x.dtype)      # (NG,k,G,S)
            # per-group dispatch matmul, contracting token AND choice
            # dims at once (no tiled token copy); the transpose is
            # layout-regular (leading dims only) — HBM-rate.
            buckets = jnp.einsum("gkts,gth->gsh", disp,
                                 x2d.reshape(NG, G, H))
            buckets = buckets.reshape(NG, E, capg, H).transpose(
                1, 0, 2, 3).reshape(E, cap, H)
    elif dispatch == "einsum":
        with scope("moe_route_onehot"):
            # position of each token within its expert's bucket
            onehot = jax.nn.one_hot(expert, E, dtype=jnp.int32)  # (N, E)
            pos = jnp.cumsum(onehot, axis=0) * onehot - 1         # (N, E)
            kept = (pos < cap) & (onehot > 0)                     # (N, E)
            # (N, E, C) dispatch mask
            disp = kept[..., None] & (jax.nn.one_hot(
                jnp.clip(pos, 0, cap - 1), cap, dtype=jnp.bool_))
            disp = disp.astype(x.dtype)
        with scope("moe_dispatch"):
            buckets = jnp.einsum("nec,nh->ech", disp, x2d)       # (E, C, H)
    elif dispatch == "sort":
        with scope("moe_dispatch"):
            # Stable sort groups tokens by expert in original order, so
            # position-within-group == the cumsum position the drop rule
            # is defined by.
            order = jnp.argsort(expert, stable=True)             # (N,)
            sorted_e = expert[order]
            counts = jnp.bincount(expert, length=E)
            starts = jnp.cumsum(counts) - counts                 # exclusive
            pos = jnp.arange(N) - starts[sorted_e]
            keep = pos < cap
            # kept tokens scatter to their slot; dropped ones target the
            # out-of-bounds index E*cap, which mode="drop" discards (no
            # trash-row write whose winner would be unspecified).
            slot = jnp.where(keep, sorted_e * cap + jnp.minimum(pos, cap - 1),
                             E * cap)
            buckets = jnp.zeros((E * cap, H), x.dtype).at[slot].set(
                x2d[order], mode="drop")
            buckets = buckets.reshape(E, cap, H)
    else:
        raise ValueError(f"unknown dispatch {dispatch!r}")

    with scope("moe_a2a_out"):
        # regroup buckets by owning device: (ep, E_local, C, H) split on
        # the device dim → every device receives its experts' buckets
        # from the whole group, stacked on a new leading dim.
        recv = buckets.reshape(ep, E_local, cap, H)
        if axis:
            recv = C.all_to_all(recv, axis, split_axis=0, concat_axis=0,
                                tiled=False)                   # (ep, El, C, H)

    with scope("moe_expert_mlp"):
        toks = recv.transpose(1, 0, 2, 3).reshape(E_local, ep * cap, H)
        # per-expert matmuls: vmap the same precision resolver the
        # attention projections use (ops/quant.py) over the expert dim —
        # one precision string selects one impl everywhere (bf16 included:
        # vmap of a plain matmul lowers to the same batched dot_general).
        from ..ops.quant import resolve_quantized_dense
        pe_dense = jax.vmap(resolve_quantized_dense(matmul_precision))
        h_gate = pe_dense(toks, w_gate)
        h_up = pe_dense(toks, w_up)
        out = pe_dense(jax.nn.silu(h_gate) * h_up,
                       w_down)                                 # (El, ep*C, H)

    with scope("moe_a2a_back"):
        back = out.reshape(E_local, ep, cap, H).transpose(1, 0, 2, 3)
        if axis:
            back = C.all_to_all(back, axis, split_axis=0, concat_axis=0,
                                tiled=False)                   # (ep, El, C, H)
        ret = back.reshape(E * cap, H)

    with scope("moe_combine"):
        if dispatch == "grouped":
            # undo the leading-dim transpose, then one combine matmul per
            # group — the exact adjoint of the dispatch einsum; the k
            # assignment outputs sum gate-weighted per token.
            back_g = ret.reshape(E, NG, capg, H).transpose(
                1, 0, 2, 3).reshape(NG, E * capg, H)
            ya = jnp.einsum("gkts,gsh->gkth", disp, back_g)
            gates_g = gates.reshape(NG, G, top_k).transpose(0, 2, 1)
            y2d = jnp.sum(ya * gates_g[..., None].astype(ya.dtype),
                          axis=1).reshape(N, H)
        elif dispatch == "einsum":
            y2d = jnp.einsum("nec,ech->nh", disp,
                             ret.reshape(E, cap, H)) * gate[:, None]
        else:
            pulled = jnp.concatenate([ret, jnp.zeros((1, H), ret.dtype)])
            y_sorted = pulled[slot] * keep[:, None].astype(ret.dtype)
            # O(N) inverse of the sort permutation (not a second sort)
            inv = jnp.zeros((N,), order.dtype).at[order].set(
                jnp.arange(N, dtype=order.dtype))
            y2d = y_sorted[inv] * gate[:, None]

    with scope("moe_aux_loss"):
        # Switch load-balance: fraction of (token, assignment) pairs per
        # expert × mean router prob per expert, summed, scaled by E;
        # averaged over the group.  top_k=1 reduces to the Switch eq. 4.
        frac = (jnp.bincount(experts.reshape(-1), length=E)
                / (N * top_k)).astype(jnp.float32)
        mean_p = jnp.mean(probs, axis=0)
        if axis:
            frac = C.all_reduce(frac, axis, mean=True)
            mean_p = C.all_reduce(mean_p, axis, mean=True)
        aux = E * jnp.sum(frac * mean_p)
        if router_z_ratio:
            # the z term rides the SAME aux channel (callers multiply by
            # the balance weight), pre-divided so the configured z weight
            # lands exactly: ratio = z_weight / aux_weight
            z = router_z_loss(x2d, w_router)
            if axis:
                z = C.all_reduce(z, axis, mean=True)
            aux = aux + router_z_ratio * z
    return y2d.reshape(B, S, H).astype(x.dtype), aux


def moe_layer(params: MoEParams, x, axis: str = "ep", *,
              capacity_factor: float = 2.0, dispatch: str = "grouped",
              group_size: int = 128, top_k: int = 1,
              router_z_ratio: float = 0.0):
    """Apply the expert-parallel MoE MLP to local tokens ``x`` (B, S, H)
    (shard_map only).  Returns (y, aux_loss)."""
    return moe_mlp(x, params.w_router, params.w_gate, params.w_up,
                   params.w_down, axis=axis,
                   capacity_factor=capacity_factor, dispatch=dispatch,
                   group_size=group_size, top_k=top_k,
                   router_z_ratio=router_z_ratio)


def moe_reference(params: MoEParams, x, *, capacity_factor: float = 2.0):
    """Single-device semantics oracle for the GLOBAL-capacity drop rule
    ("sort"/"einsum" dispatch, and "grouped" whenever the local chunk
    fits one group, N <= group_size), computed densely with FULL expert
    weights (E on dim 0), no collectives.  NOT an oracle for multi-group
    "grouped" at tight capacity — that path enforces capacity per group
    and is pinned instead by
    ``test_grouped_dispatch_matches_per_group_einsum``."""
    B, S, H = x.shape
    N = B * S
    E = params.w_router.shape[1]
    cap = int(-(-N * capacity_factor // E))
    x2d = x.reshape(N, H)
    gates, experts, _ = _route_topk(x2d, params.w_router, 1)
    gate, expert = gates[:, 0], experts[:, 0]
    onehot = jax.nn.one_hot(expert, E, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1
    kept = ((pos < cap) & (onehot > 0)).any(axis=1)
    h_g = jnp.einsum("nh,nhf->nf", x2d,
                     params.w_gate[expert])
    h_u = jnp.einsum("nh,nhf->nf", x2d, params.w_up[expert])
    out = jnp.einsum("nf,nfh->nh", jax.nn.silu(h_g) * h_u,
                     params.w_down[expert])
    y = out * gate[:, None] * kept[:, None]
    return y.reshape(B, S, H).astype(x.dtype)


def moe_lm_specs(params, axis: str = "ep") -> dict:
    """PartitionSpec tree for the MoE transformer: expert-stacked layer
    leaves (L, E, ...) shard the expert dim over ``axis``; the router and
    every dense leaf are replicated."""
    expert_leaves = {"w_gate", "w_up", "w_down"}

    def leaf_spec(path, leaf):
        name = next((getattr(k, "key", None) for k in reversed(path)
                     if getattr(k, "key", None)), None)
        if name in expert_leaves and leaf.ndim == 4:   # (L, E, h/F, F/h)
            return P(None, axis)
        return P()

    return jax.tree_util.tree_map_with_path(leaf_spec, params)


def shard_moe_lm_params(params, mesh: Mesh, axis: str = "ep"):
    return jax.tree.map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)),
        params, moe_lm_specs(params, axis),
        is_leaf=lambda x: isinstance(x, P))


def make_moe_lm_train_step(
    params_sharded,
    cfg,
    mesh: Mesh,
    *,
    dp_axis: str = "dp",
    ep_axis: str = "ep",
    sp_axis: str | None = None,
    lr: float = 3e-4,
    b1: float = 0.9,
    b2: float = 0.95,
    eps: float = 1e-8,
    donate: bool = True,
):
    """Jitted dp×ep step for the MoE *transformer*
    (``models.transformer`` with ``cfg.n_experts > 0``):
    ``(param_shards, opt_state, batch) -> (param_shards, opt_state, loss)``.
    Batch (input_ids, labels) sharded over BOTH axes (dp×ep is the data
    group — every device routes only its own token shard); each layer's
    MoE MLP all_to_alls tokens to the expert owners across the ep row
    and back.  Expert grads arrive via the all_to_all transposes (psum
    over dp only); dense/router grads mean-psum over the whole group.

    ``sp_axis`` makes it the dp×sp×ep step: the sequence dim additionally
    shards over ``sp_axis`` with ring attention (each device then routes
    its B_local × S_local tokens — routing is per-token, so the expert
    choreography is unchanged; only the chunk the capacity is computed
    over shrinks)."""
    import dataclasses

    from ..models import transformer as T

    T.require_dense_block(cfg, "parallel.expert.make_moe_lm_train_step")
    if not cfg.n_experts:
        raise ValueError("cfg.n_experts must be > 0 for the MoE step")
    ws_dp = int(mesh.shape[dp_axis])
    ws_ep = int(mesh.shape[ep_axis])
    if cfg.n_experts % ws_ep:
        raise ValueError(f"n_experts={cfg.n_experts} must be divisible "
                         f"by ep={ws_ep}")
    if sp_axis is None and cfg.sp_axis is not None:
        raise ValueError(
            f"cfg.sp_axis={cfg.sp_axis!r} (ring attention) but "
            f"make_moe_lm_train_step got sp_axis=None — the batch would "
            f"replicate over {cfg.sp_axis!r} and sp grads would never "
            f"sync.  Pass sp_axis={cfg.sp_axis!r} (the step sets the "
            f"ring config itself).")
    cfg = dataclasses.replace(cfg, ep_axis=ep_axis)
    n_total = ws_dp * ws_ep
    rep_axes = [dp_axis]
    if sp_axis is not None:
        cfg = dataclasses.replace(cfg, attention_impl="ring",
                                  sp_axis=sp_axis)
        n_total *= int(mesh.shape[sp_axis])
        rep_axes.append(sp_axis)
    specs = moe_lm_specs(params_sharded, ep_axis)

    def sync_grad(g, spec):
        axes = tuple(rep_axes) + ((ep_axis,) if ep_axis not in spec
                                  else ())
        return jax.lax.psum(g, axes) / n_total

    def step(shards, opt_state, batch):
        with scope("forward_backward"):
            loss, grads = jax.value_and_grad(
                lambda p: T.lm_loss(p, batch, cfg))(shards)
        with scope("loss_mean"):
            # one fused mean over every axis (equal shard sizes)
            loss = jax.lax.pmean(loss, tuple(rep_axes + [ep_axis]))
        with scope("grad_sync"):
            grads = jax.tree.map(sync_grad, grads, specs,
                                 is_leaf=lambda x: isinstance(x, P))
        with scope("opt_step"):
            shards, opt_state = optim.adam_update(
                grads, opt_state, shards, lr=lr, b1=b1, b2=b2, eps=eps,
                lr_mults=lr_mults)
        return shards, opt_state, loss

    # router LR multiplier (cfg.moe_router_lr_mult): per-leaf LR tree —
    # the same router-health knob the FSDP step honors
    lr_mults = None
    if getattr(cfg, "moe_router_lr_mult", 1.0) != 1.0:
        lr_mults = jax.tree_util.tree_map_with_path(
            lambda path, _leaf: (cfg.moe_router_lr_mult
                                 if any(getattr(k, "key", None) == "w_router"
                                        for k in path) else 1.0),
            params_sharded)
    state_specs = optim.AdamState(mu=specs, nu=specs, count=P())
    batch_spec = (P((dp_axis, ep_axis)) if sp_axis is None  # spec-ok
                  else P((dp_axis, ep_axis), sp_axis))
    sharded = C.smap(step, mesh,
                     in_specs=(specs, state_specs, batch_spec),
                     out_specs=(specs, state_specs, P()))
    return jax.jit(sharded, donate_argnums=(0, 1) if donate else ())


def make_ep_train_step(
    params_sharded: MoEParams,
    mesh: Mesh,
    *,
    axis: str = "ep",
    capacity_factor: float = 2.0,
    aux_weight: float = 0.01,
    lr: float = 1e-3,
    donate: bool = True,
):
    """Jitted EP step on the toy MoE regression
    ``(params, opt, (x, y)) -> (params, opt, loss)``: batch sharded on
    ``ep`` (each device routes its own tokens), expert grads stay local,
    router grads mean-psum across the group."""
    ws = int(mesh.shape[axis])
    specs = moe_specs(axis)

    def step(p, opt_state, batch):
        x, y = batch

        def loss_fn(p):
            out, aux = moe_layer(p, x, axis,
                                 capacity_factor=capacity_factor)
            return jnp.mean((out - y) ** 2) + aux_weight * aux

        with scope("forward_backward"):
            loss, grads = jax.value_and_grad(loss_fn)(p)
        with scope("loss_mean"):
            loss = C.all_reduce(loss, axis, mean=True)
        with scope("grad_sync"):
            # ep-sharded expert weights: each device owns its experts'
            # grads outright (tokens from the whole group arrived via
            # all_to_all, whose transpose already returned their
            # cotangents).  Replicated router: mean across the group.
            grads = jax.tree.map(
                lambda g, s: C.all_reduce(g, axis, mean=True)
                if axis not in s else g / ws,
                grads, specs, is_leaf=lambda s: isinstance(s, P))
        with scope("opt_step"):
            p, opt_state = optim.adam_update(grads, opt_state, p, lr=lr)
        return p, opt_state, loss

    state_specs = optim.AdamState(mu=specs, nu=specs, count=P())
    sharded = C.smap(step, mesh,
                     in_specs=(specs, state_specs, P(axis)),  # spec-ok
                     out_specs=(specs, state_specs, P()))
    return jax.jit(sharded, donate_argnums=(0, 1) if donate else ())
