"""Pre-flight HBM waterline prediction — per-config peak device memory
without running a step.

Two sources, in order of authority:

  * **compile-based** (:func:`predict_from_step`): XLA's own allocation
    plan via ``step.lower(...).compile().memory_analysis()`` — argument +
    output + temp buffers minus donation aliasing, the same accounting
    ``scripts/memory_waterline.py`` reads.  On backends that validate HBM
    fit at compile time (TPU) an over-budget plan surfaces as the
    compiler's ``Used X G of Y G hbm`` verdict instead — parsed through
    the shared ``utils.memory.parse_hbm_oom`` into a prediction with
    ``source="compiler_oom"``.
  * **analytic** (:func:`analytic_waterline`): a tensor-walk model over
    the architecture — params/grads/optimizer at rest plus a phase model
    of activations per remat policy and the streamed-loss buffers.  No
    lowering, no compile: this is what lets the planner and the tuner
    reject a config in microseconds instead of burning the compile that
    would OOM anyway.  Calibrated against the compiler's OOM verdicts
    on a v5e (``tests/test_tuner.py:OOM_WALL``); the compile-based
    source supersedes it whenever a compile is affordable.
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field

from ..utils.memory import GB, parse_hbm_oom

# schema of the measured-residual priors file that
# ``scripts/runs.py export-memory-priors`` emits from indexed memory
# ledgers (telemetry.memledger) — the memory twin of the tuner's
# cost_model.json
MEMORY_PRIORS_SCHEMA_VERSION = 1


def load_memory_priors(path: str) -> dict | None:
    """Parse an ``export-memory-priors`` file; None when missing,
    unreadable, or from a different schema generation (recalibration
    must never crash a planner run)."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    if not isinstance(doc, dict) or \
            doc.get("schema_version") != MEMORY_PRIORS_SCHEMA_VERSION:
        return None
    return doc


@dataclass
class WaterlinePrediction:
    """One config's predicted per-device HBM waterline."""
    gb: float
    source: str            # "memory_analysis" | "compiler_oom" | "analytic"
    fits: bool | None = None       # vs capacity_gb when known
    capacity_gb: float | None = None
    components: dict = field(default_factory=dict)  # GB breakdown

    def judge(self, capacity_gb: float | None) -> "WaterlinePrediction":
        """Fill ``fits`` against a capacity/budget (keeps a compiler OOM
        verdict's own ``fits=False`` even when no budget was given)."""
        if capacity_gb is not None:
            self.capacity_gb = capacity_gb
            self.fits = self.gb <= capacity_gb
        return self

    def to_dict(self) -> dict:
        return {"predicted_gb": round(self.gb, 3), "source": self.source,
                "fits": self.fits, "capacity_gb": self.capacity_gb,
                "components": {k: round(v, 3)
                               for k, v in self.components.items()}}


def predict_from_step(step, *args, capacity_gb: float | None = None
                      ) -> WaterlinePrediction:
    """Compile-time waterline of a jitted step: args + out + temp − alias
    from ``memory_analysis()``, or the compiler's own used-vs-capacity
    verdict when the plan itself exceeds HBM at compile."""
    try:
        compiled = step.lower(*args).compile()
    except Exception as e:  # noqa: BLE001 - only the OOM verdict is ours
        oom = parse_hbm_oom(str(e))
        if oom is None:
            raise
        needed, cap = oom
        return WaterlinePrediction(
            gb=needed, source="compiler_oom", fits=False,
            capacity_gb=capacity_gb or cap,
            components={"compiler_needed": needed})
    ma = compiled.memory_analysis()
    if ma is None:  # backend exposes no plan: caller falls back to analytic
        raise RuntimeError("backend returned no memory_analysis(); use "
                           "analytic_waterline instead")
    comp = {
        "args": ma.argument_size_in_bytes / GB,
        "out": ma.output_size_in_bytes / GB,
        "temp": ma.temp_size_in_bytes / GB,
        "alias": ma.alias_size_in_bytes / GB,
    }
    gb = comp["args"] + comp["out"] + comp["temp"] - comp["alias"]
    return WaterlinePrediction(gb=gb, source="memory_analysis",
                               components=comp).judge(capacity_gb)


# ------------------------------------------------------------- analytic

def _dtype_size(dtype) -> int:
    import jax.numpy as jnp
    return jnp.dtype(dtype).itemsize


def _per_token_dot_bytes(cfg, itemsize: int) -> int:
    """Bytes of ALL projection-matmul outputs for one token — the
    save_dots residency unit: q, k, v, attn-out, gate, up, down."""
    hd = cfg.head_dim or cfg.hidden_size // cfg.num_attention_heads
    nq, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    F = (getattr(cfg, "moe_ffn", None) or cfg.intermediate_size) \
        * max(getattr(cfg, "moe_top_k", 1), 1)
    elems = nq * hd + 2 * nkv * hd + cfg.hidden_size + 2 * F \
        + cfg.hidden_size
    return elems * itemsize


def analytic_waterline(cfg, *, batch: int, seq: int, ws: int = 1,
                       accum_steps: int = 1, state_precision: str = "full",
                       offload: str = "none", dense_grads: bool = True,
                       capacity_gb: float | None = None,
                       priors: dict | None = None,
                       mesh_plan=None
                       ) -> WaterlinePrediction:
    """Tensor-walk waterline model for one FSDP-style train step of
    ``cfg`` (any ``TransformerConfig``-shaped object) at global ``batch``
    × ``seq`` over ``ws`` devices.

    Phase model (per device): the peak is the at-rest state plus the
    policy-saved activations of ALL layers plus the scan-boundary
    residuals, plus the larger of one layer's working set and the loss
    buffers — layer workspace and loss-phase buffers never coexist, but
    remat-saved tensors live through both.  Optimizer state under
    ``offload`` in ("opt", "opt_act") counts one stacked-leaf pair of
    streaming headroom instead of full residency.

    ``priors`` is an ``export-memory-priors`` dict (see
    :func:`load_memory_priors`): its ``overall_ratio`` — median
    measured-ledger peak over analytic prediction across indexed runs —
    rescales the total the same way bench priors anchor the tuner, so
    the model recalibrates against ground truth without reweighing its
    own terms.

    ``mesh_plan`` (a ``parallel.composable.MeshPlan`` or anything with
    its ``param_shard_ways`` / ``opt_shard_ways`` / ``data_ways`` /
    ``tp`` attributes) replaces the flat-dp assumption: params at rest
    divide by the plan's param-shard ways (fsdp × tp × dp under W3),
    optimizer state by its opt-shard ways (W1+), the global batch by the
    data axes (dp × fsdp), and the per-layer working/saved activations
    by tp (Megatron shards the projection outputs).  ``mesh_plan=None``
    keeps the legacy flat-``ws`` law bit-for-bit."""
    itemsize = _dtype_size(getattr(cfg, "dtype", "bfloat16"))
    if mesh_plan is not None:
        param_ways = max(int(mesh_plan.param_shard_ways), 1)
        opt_ways = max(int(mesh_plan.opt_shard_ways), 1)
        data_ways = max(int(mesh_plan.data_ways), 1)
        tp_ways = max(int(getattr(mesh_plan, "tp", 1)), 1)
    else:
        param_ways = opt_ways = data_ways = ws
        tp_ways = 1
    P = cfg.param_count() if hasattr(cfg, "param_count") else 0
    params = P * itemsize / param_ways
    grads = params if dense_grads else 0.0

    # Adam moments: 2×params at the state dtype ("full" = params' dtype,
    # "int8" = ~1 byte/elem + per-row scales ≈ 9/8 byte).
    state_itemsize = itemsize if state_precision == "full" else 1.125
    opt = 2 * P * state_itemsize / opt_ways
    if offload in ("opt", "opt_act"):
        # parked on host; device cost = streaming headroom of roughly the
        # largest stacked leaf pair (mu+nu of one projection matrix stack)
        L = max(cfg.num_hidden_layers, 1)
        biggest = max(
            cfg.hidden_size * cfg.intermediate_size * L,
            cfg.vocab_size * cfg.hidden_size) * state_itemsize
        opt = 2 * biggest / opt_ways

    b = max(batch // data_ways, 1)              # per-device batch
    micro = max(b // max(accum_steps, 1), 1)    # per-microbatch rows
    H, L = cfg.hidden_size, cfg.num_hidden_layers
    hd = cfg.head_dim or H // cfg.num_attention_heads
    nq = cfg.num_attention_heads

    # scan-boundary residuals: one (micro, S, H) per layer survives the
    # forward under every remat policy
    boundaries = L * micro * seq * H * itemsize

    # policy-saved tensors (live through backward, additive with loss)
    policy = getattr(cfg, "remat_policy", "full")
    remat_on = getattr(cfg, "remat", True)
    dot_bytes = _per_token_dot_bytes(cfg, itemsize)
    saved = 0.0
    if not remat_on:
        saved = L * micro * seq * dot_bytes            # everything lives
    elif policy == "save_attn":
        saved = L * micro * seq * nq * hd * itemsize
    elif policy == "save_dots":
        saved = L * micro * seq * dot_bytes
    elif policy == "save_dots_q8":
        # int8 codes + per-row f32 scales ≈ 1.1 byte per saved element
        saved = L * micro * seq * dot_bytes / itemsize * 1.1
    if offload == "opt_act" and policy in ("save_attn", "save_dots_q8"):
        saved = 0.0                                    # parked on host
    precision = str(getattr(cfg, "matmul_precision", "bf16"))
    # low-precision matmuls (int8 STE or fp8 e4m3/e5m2) keep 1-byte
    # operand code copies for the bwd dots — same working-set shape, so
    # both precisions share the multiplier; they ride the saved-dots
    # budget when remat keeps those (save_dots_q8's saved tensors
    # already ARE the int8 codes: no extra)
    lp_mm = precision.startswith("int8") or precision.startswith("fp8")
    if lp_mm and policy == "save_dots":
        saved *= 1.5
    if remat_on and getattr(cfg, "attention_impl", "xla") == "flash":
        # the splash kernel's own residuals (output + fp32 log-sum-exp)
        # stay on the device under every policy, so that the backward
        # does not re-run the kernel (transformer.resolve_remat_policy)
        saved += L * micro * seq * nq * (hd * itemsize + 4)

    # one layer's transient working set (freed before the loss phase);
    # low-precision matmuls add the live microbatch's quantize buffers
    working = micro * seq * dot_bytes * (1.5 if lp_mm else 1.0)
    impl = getattr(cfg, "attention_impl", "xla")
    if impl == "xla":
        # unfused attention materializes fp32 scores (B, n, S, S)
        working += micro * nq * seq * seq * 4
    elif impl == "flash" and seq % 128 == 0:
        # the splash kernel's fused backward writes one dq partial a KV
        # block, each of q's shape and dtype, and sums them outside it
        # (a window that is not lane-aligned the kernel refuses).  They
        # live beside q, k, v, the output, its cotangent and the three
        # gradients, not beside the MLP's tensors, so the phase counts
        # only where it is the larger one: a v5e's compiler plans no
        # more for SMOLLM3_3B_L8's step with 1 GB of them than without
        from ..models.transformer import flash_backward_blocks
        _, block_kv, _ = flash_backward_blocks(seq)
        partials = (seq // block_kv) * nq
        working = max(working, micro * seq * hd * itemsize * (
            4 * (nq + cfg.num_key_value_heads) + partials))
    # tp shards every projection output (and its heads) column-wise, so
    # both the policy-saved dots and the live working set divide by it
    saved /= tp_ways
    working /= tp_ways

    # loss-phase buffers: the streamed head's row block of fp32 logits
    # over the whole vocabulary (tokens x chunk x 4 bytes is its budget)
    # plus the fp32 dW carry summed over the blocks, or the dense
    # 3-spike trio
    chunk = getattr(cfg, "loss_vocab_chunk", None)
    V = cfg.vocab_size
    if chunk:
        loss = micro * seq * chunk * 4 + V * H * 4
    else:
        loss = micro * seq * V * 4 * 3.0

    batch_bytes = b * seq * 4 * 2                      # int32 ids+labels
    total = (params + grads + opt + boundaries + saved
             + max(working, loss) + batch_bytes)
    comp = {
        "params": params / GB, "grads": grads / GB, "opt": opt / GB,
        "boundaries": boundaries / GB, "saved_activations": saved / GB,
        "layer_working": working / GB, "loss": loss / GB,
        "batch": batch_bytes / GB,
    }
    gb = total / GB
    if priors:
        try:
            ratio = float(priors.get("overall_ratio") or 0.0)
        except (TypeError, ValueError):
            ratio = 0.0
        if ratio > 0:
            gb *= ratio
            comp["priors_ratio"] = ratio
    return WaterlinePrediction(gb=gb, source="analytic",
                               components=comp).judge(capacity_gb)


def predict(cfg=None, *, step=None, args=(), capacity_gb=None,
            **analytic_kw) -> WaterlinePrediction:
    """One-call form: compile-based when a ``step`` (+ example args) is
    given and the backend can plan it, analytic from ``cfg`` otherwise —
    a compile that dies on a *non*-OOM error also degrades to analytic
    when a cfg is at hand (the 'compile itself OOMs host-side' case)."""
    if step is not None:
        try:
            return predict_from_step(step, *args, capacity_gb=capacity_gb)
        except Exception:  # noqa: BLE001 - analytic is the safety net
            if cfg is None:
                raise
    if cfg is None:
        raise ValueError("predict() needs a step or a model cfg")
    return analytic_waterline(cfg, capacity_gb=capacity_gb, **analytic_kw)
