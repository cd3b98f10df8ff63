"""Generate CollectiveContracts from partition RuleSets.

The hand-registered :data:`~.contracts.CONTRACTS` formulas were each
calibrated against one lowered step.  This module derives the same
contracts *structurally* from :data:`~.rules.RULESETS`: which leaves a
strategy shards at rest (gather sites), how its ``weight_update_sharding``
level moves the gradient reduction (all_reduce vs reduce_scatter vs
nothing-at-rank), and which wire format / overlap decomposition its
config picks — so a new axis combination costs a RuleSet entry, not a
hand-calibrated formula.

:func:`diff_all_contracts` is the proof the generator is trustworthy: it
evaluates generated vs hand contracts field-by-field over a synthetic
:class:`~.contracts.ContractContext` grid covering every registered
strategy and reports any divergence.  Each divergence is either a
generator bug or a latent calibration bug in the hand contract — the
tier-1 ``rules`` tests pin the diff to empty.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable

from .contracts import (
    CONTRACTS,
    CollectiveContract,
    ContractContext,
    KINDS,
    N_PROJ_LEAVES,
    _offload_host_transfers,
    ddp_bucket_count,
)
from .rules import RULESETS, RuleSet

# ---------------------------------------------------------------- counts
#
# Shared structural facts the derivations lean on, with the calibrated
# constants they produce:
#   * scanned train steps collapse depth: one site per stacked leaf;
#   * remat re-runs forward gathers inside the backward scan (2x hop
#     upper bounds for ring decompositions, n-1 backward re-gathers for
#     the per-layer W3 MLP whose last bias needs no recompute);
#   * the toy-MLP data-parallel steps carry a loss-mean all_reduce and a
#     step barrier (+2); the scanned transformer steps carry only the
#     loss pmean (+1); serving carries neither.


def _grad_buckets(c: ContractContext) -> int:
    """Flat ~MB gradient bucket count for the bucketed/q8 wire formats
    (per dtype group when the run recorded a dtype split)."""
    import numpy as np
    bucket_mb = float(c.extra.get("bucket_mb") or 25.0)
    dtype_bytes = c.extra.get("dtype_bytes")
    if dtype_bytes:
        return sum(ddp_bucket_count(b, bucket_mb, np.dtype(dt).itemsize)
                   for dt, b in dtype_bytes.items())
    return ddp_bucket_count(c.param_bytes, bucket_mb)


def _data_counts(rs: RuleSet) -> Callable[[ContractContext], dict]:
    """Data-parallel family: the ``weight_update_sharding`` axis of
    arXiv:2004.13336 decides where the gradient lands and what must be
    rebuilt, the ``grad_comm`` knob decides the W0 wire format."""
    w = rs.weight_update_sharding
    comm = rs.config.get("grad_comm", "allreduce")

    def counts(c: ContractContext) -> dict:
        n = c.n_leaves
        if w == 0:
            # replicated update: grads cross the wire, params never do
            if comm == "bucketed":
                return {"all_reduce": _grad_buckets(c) + 2}
            if comm == "q8":
                # int8 codes + f32 scale ride gathers per flat bucket;
                # only loss mean + barrier stay all_reduces
                return {"all_reduce": 2, "all_gather": 2 * _grad_buckets(c)}
            return {"all_reduce": n + 2}
        if w == 1:
            # sharded opt state: n grad all_reduces + n param rebuilds
            if c.extra.get("rebuild", "broadcast") == "all_gather":
                return {"all_reduce": n + 2, "all_gather": n}
            return {"all_reduce": 2 * n + 2}  # masked-psum broadcast twin
        if w == 2:
            # + sharded reduction: grads reduce_scatter straight to chunk
            if c.extra.get("rebuild", "broadcast") == "all_gather":
                return {"all_reduce": 2, "all_gather": n,
                        "reduce_scatter": n}
            return {"all_reduce": n + 2, "reduce_scatter": n}
        # W3, per-layer materialize: n fwd gathers + (n-1) remat'd bwd
        # re-gathers (the last bias has no recompute consumer), grads
        # arrive through the gather transpose (one psum_scatter each)
        return {"all_reduce": 2, "all_gather": 2 * n - 1,
                "reduce_scatter": n}

    return counts


def _fsdp_counts(rs: RuleSet) -> Callable[[ContractContext], dict]:
    """FSDP family: one gather + one reduce-scatter site per stacked
    leaf (scan collapses depth), one loss pmean; the overlap knob
    rewrites gather sites into ring ppermute hops, optionally fusing the
    projection matmuls into the ring."""
    overlap = rs.config.get("overlap", "none")
    axis = rs.axes[0]

    def counts(c: ContractContext) -> dict:
        n = c.n_leaves
        ws = c.axis_sizes.get(axis, c.ws)
        if overlap == "ring":
            hops = n * (ws - 1)
            return {"all_reduce": 1, "reduce_scatter": n,
                    "collective_permute": (hops, 2 * hops)}
        if overlap == "ring_fused_pallas":
            # the 7 dense projection leaves never materialize: fwd hop
            # ring (all_gather_matmul) + bwd dW ring each, no
            # gather/scatter sites; the rest keep the plain ring
            unfused = n - N_PROJ_LEAVES
            hops = (unfused + 2 * N_PROJ_LEAVES) * (ws - 1)
            return {"all_reduce": 1, "reduce_scatter": unfused,
                    "collective_permute": (hops, 2 * hops)}
        # a remat'd, resharding layer scan gathers its leaves again in
        # the backward (contracts._fsdp_counts)
        regather = int(c.extra.get("regather_leaves", 0))
        return {"all_reduce": 1, "all_gather": n + regather,
                "reduce_scatter": n}

    return counts


def _tp_counts(rs: RuleSet) -> Callable[[ContractContext], dict]:
    """Megatron TP: 2 activation rejoin sites per (scanned) layer body +
    per-leaf grad psums; never a param gather.  The overlap knob rewrites
    the 2 rejoin sites (ring: psum_scatter + ppermute hops; q8: two-shot
    quantized gathers of codes + scales)."""
    overlap = rs.config.get("overlap", "none")

    def counts(c: ContractContext) -> dict:
        n = c.n_leaves
        if overlap == "ring":
            tp = c.axis_sizes.get("tp", 2)
            return {"all_reduce": (n, n + 6), "reduce_scatter": 2,
                    "collective_permute": 2 * (tp - 1)}
        if overlap == "q8":
            return {"all_reduce": (n, n + 6), "all_gather": 4}
        return {"all_reduce": (n + 2, n + 8)}

    return counts


def _sp_counts(rs: RuleSet) -> Callable[[ContractContext], dict]:
    """fsdp placement over dp + the KV ring over sp: fsdp's sites, the
    loss pmean joined by per-leaf sp grad psums (+2 -> n+2), and the
    ring's 4 ppermute sites (k and v, forward + backward)."""
    def counts(c: ContractContext) -> dict:
        n = c.n_leaves
        return {"all_reduce": n + 2, "all_gather": n,
                "reduce_scatter": n, "collective_permute": 4}
    return counts


def _composable_counts(rs: RuleSet) -> Callable[[ContractContext], dict]:
    """dp×fsdp×tp (``parallel.composable._make_dp_fsdp_tp_step``): the
    fsdp mechanism contributes one gather + one reduce-scatter site per
    stacked leaf (scan collapses depth, backward re-gathers share the
    forward sites); the tp layer body its 2 rejoin psums; the grad sync
    one fused psum per leaf over the axes it is replicated on; plus the
    loss pmean.  The rejoin/pmean psums fuse unpredictably across
    remat boundaries, hence the range on all_reduce (mirroring the hand
    tp family's calibration)."""
    def counts(c: ContractContext) -> dict:
        n = c.n_leaves
        return {"all_reduce": (n + 1, n + 8), "all_gather": n,
                "reduce_scatter": n}
    return counts


def _moe_counts(rs: RuleSet) -> Callable[[ContractContext], dict]:
    """Switch-MoE: a2a dispatch + return in the scanned body, each with
    its backward transpose (4 sites); dense/router grads psum'd."""
    def counts(c: ContractContext) -> dict:
        n = c.n_leaves
        return {"all_reduce": (n + 2, n + 8), "all_to_all": 4}
    return counts


def _serve_counts(rs: RuleSet) -> Callable[[ContractContext], dict]:
    """Serving decode: inference-only and UNROLLED over layers (static
    layer index into the KV pools), so the 2 rejoin psums scale with
    depth instead of collapsing like the scanned train steps."""
    def counts(c: ContractContext) -> dict:
        return {"all_reduce": 2 * c.n_layers}
    return counts


def _pipeline_counts(rs: RuleSet) -> Callable[[ContractContext], dict]:
    return lambda c: {}


_FAMILY_COUNTS = {
    "data": _data_counts,
    "fsdp": _fsdp_counts,
    "tp": _tp_counts,
    "sp": _sp_counts,
    "composable": _composable_counts,
    "moe": _moe_counts,
    "serve": _serve_counts,
    "pipeline": _pipeline_counts,
}


# ------------------------------------------------------------- generation

def generate_contract(strategy: str) -> CollectiveContract:
    """Derive the CollectiveContract for ``strategy`` from its RuleSet —
    same dataclass, same evaluate/check machinery as the hand registry."""
    rs = RULESETS.get(strategy)
    if rs is None:
        raise KeyError(f"no RuleSet registered for {strategy!r}; "
                       f"have {sorted(RULESETS)}")
    counts = _FAMILY_COUNTS[rs.family](rs)

    # Full-param gathers are by-design exactly when weights are sharded
    # at rest and the step materializes them per layer: W3 (flat chunks
    # or named dims) and the sp composite that embeds fsdp.
    gathers_params = (rs.weight_update_sharding >= 3
                      or rs.family in ("fsdp", "sp"))

    # Payload estimate is param-tree-derivable only when the wire
    # traffic is the grad/param stream itself (data + fsdp families);
    # activation payloads (tp/sp/moe/serve) aren't.
    payload = None
    if rs.family == "data":
        w = rs.weight_update_sharding
        if w == 0 and rs.config.get("grad_comm") == "q8":
            payload = lambda c: c.param_bytes // 4  # int8 codes ride 1x
        elif w == 0:
            payload = lambda c: 2 * c.param_bytes   # all_reduce = 2x
        else:
            payload = lambda c: 3 * c.param_bytes   # reduce + rebuild
    elif rs.family == "fsdp":
        payload = lambda c: 3 * c.param_bytes

    host_transfers = (_offload_host_transfers
                      if rs.config.get("offload") else None)

    return CollectiveContract(
        strategy=strategy,
        axes=rs.axes,
        counts=counts,
        allows_full_param_gather=gathers_params,
        payload_bytes=payload,
        host_transfers=host_transfers,
        description=f"generated from RuleSet[{strategy}]: "
                    f"{rs.description}")


def generate_all_contracts() -> dict[str, CollectiveContract]:
    return {s: generate_contract(s) for s in RULESETS}


# ------------------------------------------------------------------ differ

def _context_grid(strategy: str) -> list[ContractContext]:
    """Synthetic contexts exercising every formula branch a strategy's
    contract can take: world sizes, leaf counts, param sizes, rebuild
    modes, bucket sizes, offload plans, layer depths."""
    rs = RULESETS[strategy]
    grids: list[ContractContext] = []

    def ctx(axis_sizes, n_leaves=12, param_bytes=4 * 2 ** 20,
            n_layers=4, **extra):
        import math
        ws = int(math.prod(axis_sizes.values())) if axis_sizes else 1
        grids.append(ContractContext(
            ws=ws, axis_sizes=dict(axis_sizes), n_leaves=n_leaves,
            n_layers=n_layers, param_bytes=param_bytes, extra=extra))

    if rs.family == "data":
        for dp in (2, 8):
            for n, pb in ((12, 123_456), (6, 4 * 2 ** 20)):
                ctx({"dp": dp}, n_leaves=n, param_bytes=pb)
                ctx({"dp": dp}, n_leaves=n, param_bytes=pb,
                    rebuild="all_gather")
                ctx({"dp": dp}, n_leaves=n, param_bytes=pb,
                    rebuild="broadcast", bucket_mb=0.05)
                ctx({"dp": dp}, n_leaves=n, param_bytes=pb,
                    bucket_mb=25.0,
                    dtype_bytes={"float32": pb // 2, "bfloat16": pb // 2})
    elif rs.family == "fsdp":
        for dp in (2, 8):
            for n in (13, 36):
                ctx({"dp": dp}, n_leaves=n)
                ctx({"dp": dp}, n_leaves=n,
                    offload={"mode": "opt", "supported": True,
                             "n_state_leaves": n, "state_bytes": 2 ** 20})
                ctx({"dp": dp}, n_leaves=n,
                    offload={"mode": "opt", "supported": False})
    elif rs.family in ("tp", "serve"):
        for tp in (2, 4, 8):
            axes = ({"tp": tp} if rs.family == "serve"
                    else {"dp": 8 // tp if tp < 8 else 1, "tp": tp})
            for n, L in ((13, 2), (13, 4)):
                ctx(axes, n_leaves=n, n_layers=L)
    elif rs.family == "sp":
        for dp, sp in ((2, 4), (4, 2)):
            ctx({"dp": dp, "sp": sp}, n_leaves=13)
    elif rs.family == "composable":
        for dp, f, tp in ((2, 2, 2), (1, 2, 2), (2, 4, 2), (2, 2, 4)):
            for n, L in ((11, 2), (11, 4)):
                ctx({"dp": dp, "fsdp": f, "tp": tp}, n_leaves=n,
                    n_layers=L)
    elif rs.family == "moe":
        for dp, ep in ((2, 4), (4, 2)):
            ctx({"dp": dp, "ep": ep}, n_leaves=16)
    else:  # pipeline
        ctx({}, n_leaves=6)
        ctx({}, n_leaves=8, n_layers=8)
    return grids


def _norm_counts(d: dict) -> dict:
    """Counts dict -> comparable form over all KINDS (missing = 0)."""
    out = {}
    for kind in KINDS:
        v = d.get(kind, 0)
        if isinstance(v, tuple):
            v = (int(v[0]), int(v[1]))
        elif v is not None:
            v = int(v)
        out[kind] = v
    return out


@dataclass
class ContractDiff:
    """Field-level divergences between the generated contract and its
    hand-registered twin for one strategy (empty = they agree)."""
    strategy: str
    divergences: list = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.divergences

    def describe(self) -> str:
        head = f"[{self.strategy}] " + ("agree" if self.ok
                                        else "DIVERGE")
        return "\n".join([head] + [f"  {d}" for d in self.divergences])


def diff_contract(strategy: str,
                  ctxs: list[ContractContext] | None = None
                  ) -> ContractDiff:
    """Cross-check generated vs hand contract for one strategy: static
    fields plus counts / payload / host-transfer evaluations over the
    context grid."""
    diff = ContractDiff(strategy)
    hand = CONTRACTS.get(strategy)
    if hand is None:
        diff.divergences.append("no hand-registered contract")
        return diff
    gen = generate_contract(strategy)
    if tuple(gen.axes) != tuple(hand.axes):
        diff.divergences.append(
            f"axes: generated {gen.axes} vs hand {hand.axes}")
    if gen.allows_full_param_gather != hand.allows_full_param_gather:
        diff.divergences.append(
            f"allows_full_param_gather: generated "
            f"{gen.allows_full_param_gather} vs hand "
            f"{hand.allows_full_param_gather}")
    if (gen.host_transfers is None) != (hand.host_transfers is None):
        diff.divergences.append(
            f"host_transfers: generated "
            f"{'declared' if gen.host_transfers else 'absent'} vs hand "
            f"{'declared' if hand.host_transfers else 'absent'}")
    if (gen.payload_bytes is None) != (hand.payload_bytes is None):
        diff.divergences.append(
            f"payload_bytes: generated "
            f"{'estimated' if gen.payload_bytes else 'None'} vs hand "
            f"{'estimated' if hand.payload_bytes else 'None'}")
    for c in (ctxs if ctxs is not None else _context_grid(strategy)):
        tag = (f"ws={c.ws} axes={dict(c.axis_sizes)} n={c.n_leaves} "
               f"L={c.n_layers} extra={dict(c.extra)}")
        g, h = _norm_counts(gen.counts(c)), _norm_counts(hand.counts(c))
        for kind in KINDS:
            if g[kind] != h[kind]:
                diff.divergences.append(
                    f"counts[{kind}] @ {tag}: generated {g[kind]} vs "
                    f"hand {h[kind]}")
        if gen.payload_bytes and hand.payload_bytes:
            gp, hp = int(gen.payload_bytes(c)), int(hand.payload_bytes(c))
            if gp != hp:
                diff.divergences.append(
                    f"payload_bytes @ {tag}: generated {gp} vs hand {hp}")
        if gen.host_transfers and hand.host_transfers:
            gt, ht = gen.host_transfers(c), hand.host_transfers(c)
            if dict(gt) != dict(ht):
                diff.divergences.append(
                    f"host_transfers @ {tag}: generated {gt} vs "
                    f"hand {ht}")
    return diff


def diff_all_contracts() -> dict[str, ContractDiff]:
    """The full cross-check: every strategy known to either registry
    (one-sided registrations count as divergences)."""
    out = {}
    for strategy in sorted(set(CONTRACTS) | set(RULESETS)):
        if strategy not in RULESETS:
            d = ContractDiff(strategy)
            d.divergences.append("hand contract has no RuleSet twin")
            out[strategy] = d
        else:
            out[strategy] = diff_contract(strategy)
    return out


# --------------------------------------------------- generated registry
#
# The composable mesh driver's strategies have NO hand-written contract
# by design (the tentpole of ROADMAP item 1): their registry entry IS
# the generated one, installed at import time so evaluate_contract /
# hlo_lint / the drift differ see them exactly like any calibrated
# strategy.  diff_contract for these trivially agrees — the point is
# that the formula's provenance is the RuleSet, not a calibration pass.
GENERATED_STRATEGIES = ("composable_zero1", "composable_dp_fsdp_tp")

for _name in GENERATED_STRATEGIES:
    CONTRACTS[_name] = generate_contract(_name)
del _name
