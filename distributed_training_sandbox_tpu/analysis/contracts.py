"""Per-strategy collective choreography contracts.

A :class:`CollectiveContract` states, declaratively, what one optimizer
step of a strategy is allowed to put on the wire: which collective kinds
appear at how many *sites* in the lowered StableHLO, over which mesh
axes, and roughly how many bytes.  The counts are **site counts** — the
number the tests and every script's startup print already compute via
``ops.hlo.count_collectives`` — so a ``lax.scan`` over layers contributes
its body's collectives once regardless of depth (that is also why the
counts are stable across model sizes of the same family).

The formulas mirror the reference's prose collective accounting
(reference ``README.md:16-20``: "+60 all_reduce +60 broadcast" for 12
params × 5 steps of ZeRO-1) but are evaluated mechanically: a refactor
that silently replicates a sharded param (an extra all-gather) or drops
a reduce-scatter fails the contract instead of drifting by eye.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Callable, Mapping

KINDS = ("all_reduce", "all_gather", "reduce_scatter",
         "collective_permute", "all_to_all")


def _tree_stats(params) -> tuple[int, int]:
    """(leaf count, total param bytes) of a pytree of arrays."""
    import jax
    leaves = [l for l in jax.tree.leaves(params) if hasattr(l, "shape")]
    nbytes = sum(math.prod(l.shape) * getattr(l.dtype, "itemsize", 4)
                 for l in leaves)
    return len(leaves), int(nbytes)


@dataclass(frozen=True)
class ContractContext:
    """Everything a contract formula may depend on, captured from the run
    being checked: world size, mesh axis sizes, parameter tree stats and
    strategy knobs (``extra`` — e.g. the ZeRO rebuild mode)."""
    ws: int = 1
    axis_sizes: Mapping[str, int] = field(default_factory=dict)
    n_leaves: int = 0
    n_layers: int = 0
    param_bytes: int = 0
    extra: Mapping[str, Any] = field(default_factory=dict)

    @classmethod
    def capture(cls, *, params=None, mesh=None, n_layers: int = 0,
                **extra) -> "ContractContext":
        n_leaves = param_bytes = 0
        if params is not None:
            n_leaves, param_bytes = _tree_stats(params)
        axis_sizes = dict(mesh.shape) if mesh is not None else {}
        ws = int(math.prod(axis_sizes.values())) if axis_sizes else 1
        return cls(ws=ws, axis_sizes=axis_sizes, n_leaves=n_leaves,
                   n_layers=n_layers, param_bytes=param_bytes, extra=extra)


@dataclass(frozen=True)
class CollectiveContract:
    """Declarative choreography of one strategy's train step.

    ``counts(ctx)`` maps collective kind -> expected StableHLO site
    count: an int (exact), a ``(lo, hi)`` range (inclusive), or None
    (unchecked).  Kinds missing from the dict are expected to be 0.
    ``axes``: the mesh axes this strategy's collectives may span —
    the replica-group check in ``hlo_lint`` enforces it on compiled HLO.
    ``allows_full_param_gather``: strategies that materialize full params
    by design (ZeRO-3 / FSDP / SP) — exempt from the replication lint.
    ``payload_bytes(ctx)``: approximate per-step bytes on the wire, for
    the manifest / report (informational, never asserted).
    ``host_transfers(ctx)``: declared MoveToHost/MoveToDevice custom-call
    count ranges for strategies whose choreography *includes* host
    offload (``memory_plan.OffloadPlan.host_transfer_counts``, read off
    ``ctx.extra["offload"]``) — turns ``hlo_lint``'s host-transfer check
    from forbid into count-check; None keeps the strict forbid."""
    strategy: str
    axes: tuple[str, ...]
    counts: Callable[[ContractContext], dict]
    allows_full_param_gather: bool = False
    payload_bytes: Callable[[ContractContext], int] | None = None
    host_transfers: Callable[[ContractContext], dict] | None = None
    description: str = ""


# ---------------------------------------------------------------- registry
#
# Calibrated against the lowered steps of the in-repo factories (see
# tests/test_contracts.py, which re-derives several of these by lowering
# on the CPU mesh).  n = param leaf count throughout.

def ddp_bucket_count(param_bytes: int, bucket_mb: float,
                     itemsize: int = 4) -> int:
    """Expected all-reduce *site* count of ``parallel.ddp.bucket_gradients``
    for one dtype group: the concatenated flat gradient vector is split
    into exact-capacity chunks of ``bucket_mb`` MB, so the count is just
    ``ceil(elements / chunk_elements)``.  Mirrors the implementation's
    integer arithmetic (capacity floors to whole elements)."""
    cap_elems = max(int(bucket_mb * 2 ** 20) // itemsize, 1)
    n_elems = -(-int(param_bytes) // itemsize)
    return max(-(-n_elems // cap_elems), 1)


def _ddp_bucketed_counts(c: ContractContext) -> dict:
    """Bucketed grad sync + loss mean + barrier.  ``bucket_mb`` comes from
    the run's knobs (ctx.extra); ``dtype_bytes`` (dtype name -> bytes) may
    refine the formula for mixed-precision trees, else all param bytes are
    assumed one 4-byte dtype — exact for the fp32 toy models."""
    import numpy as np
    bucket_mb = float(c.extra.get("bucket_mb") or 25.0)
    dtype_bytes = c.extra.get("dtype_bytes")
    if dtype_bytes:
        n = sum(ddp_bucket_count(b, bucket_mb, np.dtype(dt).itemsize)
                for dt, b in dtype_bytes.items())
    else:
        n = ddp_bucket_count(c.param_bytes, bucket_mb)
    return {"all_reduce": n + 2}


def _ddp_q8_counts(c: ContractContext) -> dict:
    """int8 quantized grad sync: per flat bucket one all_gather of the
    int8 codes + one of the f32 scale (the loss mean + barrier stay
    all_reduces).  Bucket count = the same closed formula as
    ddp_bucketed (capacity floors to whole elements of the ORIGINAL
    grad dtype — quantization happens after bucketing)."""
    import numpy as np
    bucket_mb = float(c.extra.get("bucket_mb") or 25.0)
    dtype_bytes = c.extra.get("dtype_bytes")
    if dtype_bytes:
        n = sum(ddp_bucket_count(b, bucket_mb, np.dtype(dt).itemsize)
                for dt, b in dtype_bytes.items())
    else:
        n = ddp_bucket_count(c.param_bytes, bucket_mb)
    return {"all_reduce": 2, "all_gather": 2 * n}


def _fsdp_counts(c: ContractContext) -> dict:
    """One gather + one reduce-scatter site per param leaf (the scan
    collapses depth), one loss pmean.  A rematerialized layer scan that
    reshards after forward runs the per-layer gathers a second time in
    the backward — ``extra["regather_leaves"]``, the number of stacked
    layer leaves then, 0 when remat is off or the gathers are hoisted
    out of the scan."""
    return {"all_reduce": 1,
            "all_gather": c.n_leaves + int(c.extra.get("regather_leaves",
                                                       0)),
            "reduce_scatter": c.n_leaves}


def _fsdp_ring_counts(c: ContractContext) -> dict:
    """fsdp with the gathers ring-decomposed: every all_gather site
    becomes ws-1 collective_permute hops (rank-order chunk placement);
    the backward stays the monolithic psum_scatter per leaf (pinned by
    the ring op's custom_vjp, which is also what makes the variant
    bitwise-identical).  Remat re-runs the forward ring in the backward
    scan, hence the 2x upper bound."""
    ws = c.axis_sizes.get("dp", c.ws)
    hops = c.n_leaves * (ws - 1)
    return {"all_reduce": 1, "reduce_scatter": c.n_leaves,
            "collective_permute": (hops, 2 * hops)}


# dense transformer projection leaves per layer (wq wk wv wo w_gate
# w_up w_down) — the leaves the ring_fused modes keep sharded and run
# as collective matmuls.  Constant for the dense family; MoE is
# rejected by the fused modes' validation.
N_PROJ_LEAVES = 7


def _fsdp_ring_fused_pallas_counts(c: ContractContext) -> dict:
    """fsdp with the projection matmuls fused into the gather ring
    (Pallas chunk-matmul engine): the 7 projection leaves never
    materialize — each runs ws-1 ppermute hops forward (all_gather_matmul)
    and ws-1 backward (the dW ring of matmul_reduce_scatter's transpose);
    the remaining leaves (norms, embed, final_norm) keep the plain ring
    gather with its monolithic psum_scatter backward.  Remat re-runs
    forward rings in the backward scan, hence the 2x upper bound."""
    ws = c.axis_sizes.get("dp", c.ws)
    unfused = c.n_leaves - N_PROJ_LEAVES
    hops = (unfused + 2 * N_PROJ_LEAVES) * (ws - 1)
    return {"all_reduce": 1, "reduce_scatter": unfused,
            "collective_permute": (hops, 2 * hops)}


def _tp_q8_counts(c: ContractContext) -> dict:
    """tp with the two per-layer rejoin psums running as EQuARX two-shot
    quantized all-reduces: each rejoin site becomes 2 all_gather sites
    (int8 codes + f32 scales over the same tp group) and leaves the
    all_reduce budget to the rejoins' full-precision backward psums,
    per-leaf grad psums and the loss mean."""
    return {"all_reduce": (c.n_leaves, c.n_leaves + 6), "all_gather": 4}


def _tp_ring_counts(c: ContractContext) -> dict:
    """tp with the two per-layer rejoin psums decomposed into
    psum_scatter + ring all-gather: 2 reduce_scatter sites, tp-1 hops
    each, and the rejoins' backward psums (custom_vjp) fold into the
    same all_reduce budget the baseline's transposes used."""
    tp = c.axis_sizes.get("tp", 2)
    return {"all_reduce": (c.n_leaves, c.n_leaves + 6),
            "reduce_scatter": 2,
            "collective_permute": 2 * (tp - 1)}


def _zero1_counts(c: ContractContext) -> dict:
    if c.extra.get("rebuild", "broadcast") == "all_gather":
        return {"all_reduce": c.n_leaves + 2, "all_gather": c.n_leaves}
    # masked-psum rebuild: the wire twin of per-param dist.broadcast
    return {"all_reduce": 2 * c.n_leaves + 2}


def _zero2_counts(c: ContractContext) -> dict:
    if c.extra.get("rebuild", "broadcast") == "all_gather":
        return {"all_reduce": 2, "all_gather": c.n_leaves,
                "reduce_scatter": c.n_leaves}
    return {"all_reduce": c.n_leaves + 2, "reduce_scatter": c.n_leaves}


def _offload_host_transfers(c: ContractContext) -> dict:
    """The declared per-step MoveToHost/MoveToDevice count ranges, read
    off the :class:`memory_plan.OffloadPlan` dict the step build put in
    ``ctx.extra["offload"]``.  An unsupported-backend fallback build
    declares zero — the lint then *forbids* transfers, so the fallback
    is checked, not waved through."""
    from ..memory_plan.offload import OffloadPlan
    plan = c.extra.get("offload") or {}
    if isinstance(plan, OffloadPlan):
        return plan.host_transfer_counts()
    return OffloadPlan(
        mode=plan.get("mode", "none"),
        supported=bool(plan.get("supported")),
        n_state_leaves=int(plan.get("n_state_leaves", 0)),
        state_bytes=int(plan.get("state_bytes", 0)),
        act_names=tuple(plan.get("act_names") or ()),
    ).host_transfer_counts()


CONTRACTS: dict[str, CollectiveContract] = {
    # per-param grad all_reduce + loss mean + step barrier (DDP/ddp.py:43-47)
    "ddp": CollectiveContract(
        "ddp", ("dp",),
        lambda c: {"all_reduce": c.n_leaves + 2},
        payload_bytes=lambda c: 2 * c.param_bytes,
        description="per-param grad all_reduce; no gathers (params "
                    "replicated at rest)"),
    # grads flattened per dtype into ~bucket_mb flat buckets, one
    # all_reduce per bucket (+ loss mean + barrier) — torch DDP's bucketed
    # sync; count is a closed formula over param bytes and bucket size
    "ddp_bucketed": CollectiveContract(
        "ddp_bucketed", ("dp",), _ddp_bucketed_counts,
        payload_bytes=lambda c: 2 * c.param_bytes,
        description="ceil(param_bytes/bucket) grad all_reduces over flat "
                    "buckets + loss mean + barrier; no gathers"),
    # grads quantized to int8 in flat buckets, shipped as all_gathers of
    # (codes, per-bucket scale) and summed after the wire — ~8x less bus
    # traffic than the f32 all_reduce (EQuARX, arXiv:2506.17615)
    "ddp_q8": CollectiveContract(
        "ddp_q8", ("dp",), _ddp_q8_counts,
        # int8 codes ride a gather (1x the quantized payload on the wire)
        # vs the f32 all_reduce's 2x full payload
        payload_bytes=lambda c: c.param_bytes // 4,
        description="2 all_gathers (int8 codes + scale) per flat grad "
                    "bucket + loss mean + barrier; no f32 all_reduces "
                    "on the grad path"),
    # grads all_reduced per param, owner-chunk Adam, per-param rebuild
    "zero1": CollectiveContract(
        "zero1", ("dp",), _zero1_counts,
        payload_bytes=lambda c: 3 * c.param_bytes,
        description="n grad all_reduces + n param rebuilds "
                    "(the reference's 60+60 per 5 steps) + loss + barrier"),
    # grads reduce_scattered straight to the chunk (zero2.py:94-115)
    "zero2": CollectiveContract(
        "zero2", ("dp",), _zero2_counts,
        payload_bytes=lambda c: 3 * c.param_bytes,
        description="n grad reduce_scatters + n param rebuilds + loss + "
                    "barrier"),
    # params sharded at rest; per-layer materialize in fwd AND remat'd bwd
    # (zero3.py:56-77).  Sites: n fwd gathers + (n-1) bwd re-gathers — the
    # last layer's bias needs no recompute (no ReLU mask after it), so its
    # backward gather is dead-code-eliminated.  Grads arrive through the
    # all_gather transpose: one psum_scatter per param.
    "zero3": CollectiveContract(
        "zero3", ("dp",),
        lambda c: {"all_reduce": 2,
                   "all_gather": 2 * c.n_leaves - 1,
                   "reduce_scatter": c.n_leaves},
        allows_full_param_gather=True,
        payload_bytes=lambda c: 3 * c.param_bytes,
        description="per-layer fwd+bwd all_gathers, psum_scatter grads, "
                    "loss + barrier"),
    # per-leaf gather around compute (scan body: one site per stacked
    # leaf), reduce-scatter transposes, one loss mean (no barrier)
    "fsdp": CollectiveContract(
        "fsdp", ("dp",), _fsdp_counts,
        allows_full_param_gather=True,
        payload_bytes=lambda c: 3 * c.param_bytes,
        description="one gather + one reduce-scatter site per param leaf "
                    "(scan collapses depth; remat re-gathers the layer "
                    "leaves in the backward), one loss pmean"),
    # fsdp with --offload opt: identical collective choreography to fsdp
    # (the transfers are custom calls, not collectives) PLUS a declared
    # host-offload transfer budget — MoveToDevice streams the Adam
    # moments in for the update, MoveToHost parks them back.  Counts
    # come from the build's OffloadPlan (zero on backends without a
    # pinned_host space: the fallback step must stay transfer-free).
    "fsdp_offload": CollectiveContract(
        "fsdp_offload", ("dp",), _fsdp_counts,
        allows_full_param_gather=True,
        payload_bytes=lambda c: 3 * c.param_bytes,
        host_transfers=_offload_host_transfers,
        description="fsdp choreography + declared MoveToHost/MoveToDevice "
                    "streaming of host-resident optimizer state"),
    # fsdp with matmul_precision=fp8: the e4m3/e5m2 scaled matmuls live
    # entirely inside the dense seam — the WIRE choreography is exactly
    # fsdp's (the precision leg changes flops and working set, not
    # collectives), which is precisely what this contract pins down
    "fsdp_fp8": CollectiveContract(
        "fsdp_fp8", ("dp",), _fsdp_counts,
        allows_full_param_gather=True,
        payload_bytes=lambda c: 3 * c.param_bytes,
        description="fsdp choreography unchanged: fp8 scaling is local "
                    "to the dense seam, any site delta is a leak"),
    # fsdp with --overlap ring_fused_pallas: projection leaves fused
    # into collective matmuls with the Pallas chunk-matmul engine — the
    # ppermute hops stay at the XLA level (CPU interpret has no remote
    # DMA), so the wire counts match the fused choreography, not the
    # kernel impl
    "fsdp_ring_fused_pallas": CollectiveContract(
        "fsdp_ring_fused_pallas", ("dp",),
        _fsdp_ring_fused_pallas_counts,
        allows_full_param_gather=True,
        payload_bytes=lambda c: 3 * c.param_bytes,
        description="7 projection leaves as fused ring matmuls (fwd + "
                    "bwd hop rings, no gather/scatter sites), plain "
                    "ring + psum_scatter for the rest, one loss pmean"),
    # fsdp with --overlap ring: the overlap engine's decomposed gathers
    # (ops.collectives.ring_all_gather) — ppermute hops instead of
    # monolithic all_gathers, bitwise-identical losses
    "fsdp_ring": CollectiveContract(
        "fsdp_ring", ("dp",), _fsdp_ring_counts,
        allows_full_param_gather=True,
        payload_bytes=lambda c: 3 * c.param_bytes,
        description="(ws-1) ppermute hops per gathered leaf, monolithic "
                    "psum_scatter backward per leaf, one loss pmean; "
                    "any all_gather site is a fallback to the "
                    "un-decomposed path"),
    # tp with --overlap ring: the two per-layer rejoin psums decomposed
    # into psum_scatter + ring all-gather (bitwise-identical)
    "tp_ring": CollectiveContract(
        "tp_ring", ("dp", "tp"), _tp_ring_counts,
        payload_bytes=None,
        description="2 rejoin psum_scatter sites + 2(tp-1) ppermute hops "
                    "+ per-leaf grad psums; gather/scatter of params "
                    "still forbidden"),
    # tp with --overlap q8: rejoin psums ride the wire as int8 codes +
    # scales (EQuARX two-shot, arXiv:2506.17615) — all_gather sites over
    # tp replace the 2 rejoin all_reduce sites; grads stay full-precision
    "tp_q8": CollectiveContract(
        "tp_q8", ("dp", "tp"), _tp_q8_counts,
        # two rejoins/layer-site ship int8 + f32-scale instead of f32:
        # ~4x fewer activation bus bytes (informational; activation
        # payloads aren't param-tree-derivable, so no estimate)
        payload_bytes=None,
        description="4 all_gather sites (codes + scales per rejoin) + "
                    "full-precision grad/backward psums; gather of "
                    "params still forbidden"),
    # Megatron TP: activations psum'd in the layer body (2/layer-site),
    # grads psum'd per replicated leaf; NO param gathers or scatters —
    # an all_gather here means a param silently went dp-replicated.
    "tp": CollectiveContract(
        "tp", ("dp", "tp"),
        lambda c: {"all_reduce": (c.n_leaves + 2, c.n_leaves + 8)},
        payload_bytes=None,
        description="activation psums + per-leaf grad psums only; any "
                    "gather/scatter site is a choreography break"),
    # FSDP over dp × ring attention over sp: fsdp sites + the KV ring's
    # collective_permutes (k and v, forward + backward = 4 sites) + per-
    # leaf sp grad psums (params are sp-replicated)
    "sp": CollectiveContract(
        "sp", ("dp", "sp"),
        lambda c: {"all_reduce": c.n_leaves + 2,
                   "all_gather": c.n_leaves,
                   "reduce_scatter": c.n_leaves,
                   "collective_permute": 4},
        allows_full_param_gather=True,
        payload_bytes=None,
        description="fsdp choreography + 4 KV-ring ppermute sites + sp "
                    "grad psums"),
    # switch-MoE: a2a dispatch + return in the scanned layer body, each
    # with its backward transpose (4 sites); dense/router grads psum'd
    "moe": CollectiveContract(
        "moe", ("dp", "ep"),
        lambda c: {"all_reduce": (c.n_leaves + 2, c.n_leaves + 8),
                   "all_to_all": 4},
        payload_bytes=None,
        description="4 all_to_all sites (dispatch/return × fwd/bwd) + "
                    "per-leaf grad psums; gathers/scatters forbidden"),
    # serving decode (serving.engine.make_serve_decode_step under tp):
    # inference-only, so the whole choreography is the layer body's two
    # rejoin psums — and the layer stack is UNROLLED (static layer index
    # into the per-layer KV pools), so the sites scale with depth instead
    # of collapsing like the scanned train steps.  Params stay sharded at
    # rest: any gather/scatter site means a weight went replicated, and
    # any dp-axis collective means requests leaked across slots.
    "serve_decode": CollectiveContract(
        "serve_decode", ("tp",),
        lambda c: {"all_reduce": 2 * c.n_layers},
        payload_bytes=None,
        description="2 activation psums per (unrolled) layer over tp "
                    "only; no grads, so no other collective may appear"),
    # serve_decode with the Pallas paged-attention kernel: attention
    # reads KV pages in place inside the kernel — pure local compute,
    # so the wire choreography is bitwise serve_decode's
    "serve_decode_paged_kernel": CollectiveContract(
        "serve_decode_paged_kernel", ("tp",),
        lambda c: {"all_reduce": 2 * c.n_layers},
        payload_bytes=None,
        description="2 activation psums per (unrolled) layer over tp "
                    "only; the paged kernel adds zero wire sites"),
    # speculative verify (serving.engine.make_serve_spec_verify_step):
    # one (B, k+1) target forward replacing k+1 sequential decode steps
    # — batching over S is slot-local compute, so the choreography is
    # bitwise serve_decode's (verification is per-row argmax; the
    # accept/rollback arithmetic runs in a separate collective-free jit)
    "serve_decode_spec": CollectiveContract(
        "serve_decode_spec", ("tp",),
        lambda c: {"all_reduce": 2 * c.n_layers},
        payload_bytes=None,
        description="2 activation psums per (unrolled) layer over tp "
                    "only; the (B, k+1) verify batch adds zero wire "
                    "sites"),
    # batched flash prefill (serving.engine.make_serve_prefill_batch_
    # step): the chunk's attention runs inside the Pallas flash kernel
    # — pages read in place, online softmax local to the shard's heads
    # — so again only the layer body's two rejoin psums hit the wire
    "serve_prefill_flash": CollectiveContract(
        "serve_prefill_flash", ("tp",),
        lambda c: {"all_reduce": 2 * c.n_layers},
        payload_bytes=None,
        description="2 activation psums per (unrolled) layer over tp "
                    "only; the flash prefill kernel adds zero wire "
                    "sites"),
    # pipeline stages are single-device jitted programs; inter-stage comm
    # is host-mediated device transfer, never a mesh collective
    "gpipe": CollectiveContract(
        "gpipe", (), lambda c: {},
        description="stage programs carry zero collectives"),
    "1f1b": CollectiveContract(
        "1f1b", (), lambda c: {},
        description="stage programs carry zero collectives"),
}


# ---------------------------------------------------------------- checking

def parse_expected_spec(value) -> tuple[int, float]:
    """One value of a serialized verdict's ``expected`` dict
    (``ContractVerdict.to_dict``: int exact, ``"lo..hi"`` range,
    ``"any"``/None unchecked) -> an inclusive ``(lo, hi)`` bound.  The
    measured-side consumers (``telemetry.ledger``'s trace join) re-check
    ranges from the manifest's already-serialized verdict, so the parse
    lives next to the serializer."""
    if value is None or value == "any":
        return 0, math.inf
    if isinstance(value, str) and ".." in value:
        lo, hi = value.split("..", 1)
        return int(lo), int(hi)
    if isinstance(value, (tuple, list)):
        return int(value[0]), int(value[1])
    return int(value), int(value)


@dataclass
class ContractVerdict:
    """Outcome of checking observed counts against one contract."""
    strategy: str
    ok: bool
    expected: dict
    observed: dict
    violations: list[str]
    payload_bytes: int | None = None

    def summary(self) -> str:
        if self.ok:
            seen = ", ".join(f"{k}={v}" for k, v in
                             sorted(self.observed.items()) if v)
            return f"OK ({seen})" if seen else "OK (no collectives)"
        return "VIOLATED: " + "; ".join(self.violations)

    def to_dict(self) -> dict:
        return {"strategy": self.strategy, "ok": self.ok,
                "expected": self.expected, "observed": self.observed,
                "violations": self.violations,
                "payload_bytes": self.payload_bytes}


def check_counts(contract: CollectiveContract, observed: Mapping[str, int],
                 ctx: ContractContext) -> ContractVerdict:
    """Compare ``count_collectives``-style observed counts against the
    contract's expectation for ``ctx``.  Kinds the contract omits must be
    0; int expectations are exact; ``(lo, hi)`` inclusive; None skipped."""
    expected = dict(contract.counts(ctx))
    violations = []
    exp_out = {}
    for kind in KINDS:
        want = expected.get(kind, 0)
        got = int(observed.get(kind, 0))
        if want is None:
            exp_out[kind] = "any"
            continue
        if isinstance(want, tuple):
            lo, hi = want
            exp_out[kind] = f"{lo}..{hi}"
            if not lo <= got <= hi:
                violations.append(
                    f"{kind}: {got} sites, contract allows {lo}..{hi}")
        else:
            exp_out[kind] = int(want)
            if got != want:
                violations.append(
                    f"{kind}: {got} sites, contract expects {want}")
    payload = (int(contract.payload_bytes(ctx))
               if contract.payload_bytes else None)
    obs = {k: int(observed.get(k, 0)) for k in KINDS}
    return ContractVerdict(strategy=contract.strategy,
                           ok=not violations, expected=exp_out,
                           observed=obs, violations=violations,
                           payload_bytes=payload)


def evaluate_contract(strategy: str, observed: Mapping[str, int], *,
                      params=None, mesh=None, n_layers: int = 0,
                      ctx: ContractContext | None = None,
                      **extra) -> ContractVerdict:
    """One-call form the strategy scripts use: look up the registry,
    capture a context from the live params/mesh, check the counts they
    already computed for their startup print."""
    if strategy not in CONTRACTS:
        raise KeyError(f"no contract registered for {strategy!r}; "
                       f"have {sorted(CONTRACTS)}")
    if ctx is None:
        ctx = ContractContext.capture(params=params, mesh=mesh,
                                      n_layers=n_layers, **extra)
    return check_counts(CONTRACTS[strategy], observed, ctx)
