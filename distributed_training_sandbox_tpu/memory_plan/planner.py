"""Auto-fit planner: search remat × accumulation × quantization × offload
for the best predicted-fitting config under an HBM budget.

Given a target (model geometry, global batch, sequence length, device
count) and a budget in GB, the planner enumerates the discrete knob space

    remat_policy  {full, save_attn, save_dots, save_dots_q8}
  × accum_steps   {1, 2, 4, ...}        (must divide the per-device batch)
  × matmul        {bf16, int8_bwd}
  × state         {full, int8}
  × offload       {none, opt, opt_act}

predicts each candidate's waterline with the *analytic* predictor (no
lowering — rejection is pre-compile by construction), drops everything
over budget, and ranks the survivors by modeled throughput: measured
step-time priors from bench JSON artifacts when a row with the same knobs
exists, a relative-speed model otherwise.
An optional ``verify`` hook re-checks the winner with the compile-based
predictor (``predict_from_step``) before anyone commits real time to it.
"""

from __future__ import annotations

import json
import re
from dataclasses import dataclass, field, replace as _dc_replace

from .predictor import WaterlinePrediction, analytic_waterline

REMAT_POLICIES = ("full", "save_attn", "save_dots", "save_dots_q8")
QUANT_CHOICES = ("bf16", "int8_bwd", "fp8")
STATE_CHOICES = ("full", "int8")
OFFLOAD_CHOICES = ("none", "opt")

# Relative step-speed multipliers, calibrated on a knob matrix taken
# before the ledger (SMOLLM3_3B_L8 @ seq 8192, v5e; rows of it are
# tests/fixtures/bench_priors/): save_dots 110.1 vs full 103.6
# bf16 TFLOPS; int8_bwd 122.0 vs 103.6; s8 state ~parity (126.2 vs 125.7);
# q8-saved dots give ~most of save_dots' win back to the round-trip.
_REMAT_SPEED = {"full": 1.00, "save_attn": 1.03, "save_dots": 1.06,
                "save_dots_q8": 1.045}
# fp8 multipliers are CPU-tier placeholders pending a TPU-measured row
# (no fp8 units on v5e — see ops/quant.py), so they sit strictly BELOW
# the measured int8_bwd anchor: a config no bench row has ever timed
# must not outrank one a row has — the same measured-beats-multiplier
# pessimism the tuner cost model applies.  Internal ordering kept:
# delayed scaling saves the per-step amax reduction over dynamic, the
# hand Pallas kernel trails XLA (matching the measured int8 kernel gap).
_QUANT_SPEED = {"bf16": 1.00, "int8_bwd": 1.18, "fp8": 1.10,
                "fp8_delayed": 1.11, "fp8_pallas": 1.05}
_STATE_SPEED = {"full": 1.00, "int8": 1.00}
# host offload pays PCIe streaming; activation offload pays it per layer
_OFFLOAD_SPEED = {"none": 1.00, "opt": 0.97, "opt_act": 0.90}
_ACCUM_OVERHEAD = 0.02     # per extra microbatch: scan + carry update cost


@dataclass(frozen=True)
class Candidate:
    """One point of the planner's discrete knob space."""
    remat_policy: str = "full"
    accum_steps: int = 1
    matmul_precision: str = "bf16"
    state_precision: str = "full"
    offload: str = "none"

    def label(self) -> str:
        parts = [self.remat_policy]
        if self.matmul_precision != "bf16":
            parts.append(self.matmul_precision)
        if self.state_precision != "full":
            parts.append("s8")
        if self.accum_steps > 1:
            parts.append(f"accum{self.accum_steps}")
        if self.offload != "none":
            parts.append(f"offload_{self.offload}")
        return "+".join(parts)

    def apply_to(self, cfg):
        """The model config with this candidate's knobs applied
        (``accum_steps``/``state_precision``/``offload`` are step-factory
        knobs — read them off the candidate when building the step)."""
        over = {"remat_policy": self.remat_policy,
                "matmul_precision": self.matmul_precision}
        if self.offload == "opt_act":
            over["offload_activations"] = True
        return _dc_replace(cfg, **over)


@dataclass
class PlannedCandidate:
    candidate: Candidate
    prediction: WaterlinePrediction
    fits: bool
    score: float                   # modeled relative throughput
    prior: dict | None = None      # measured bench row backing the score
    est_step_ms: float | None = None   # absolute, when TFLOPS-anchored

    def to_dict(self) -> dict:
        return {"config": self.candidate.label(),
                **self.prediction.to_dict(),
                "fits": self.fits, "modeled_speed": round(self.score, 4),
                "est_step_ms": round(self.est_step_ms, 1)
                if self.est_step_ms else None,
                "prior": (self.prior or {}).get("config")}


@dataclass
class Plan:
    best: PlannedCandidate | None
    rows: list = field(default_factory=list)     # every candidate, ranked
    budget_gb: float | None = None

    def to_dict(self) -> dict:
        return {"budget_gb": self.budget_gb,
                "chosen": self.best.to_dict() if self.best else None,
                "candidates": [r.to_dict() for r in self.rows]}

    def summary(self) -> str:
        n_fit = sum(r.fits for r in self.rows)
        head = (f"{n_fit}/{len(self.rows)} candidates fit "
                f"budget {self.budget_gb:.2f} GB"
                if self.budget_gb is not None
                else f"{len(self.rows)} candidates (no budget)")
        if self.best is None:
            return f"{head}; NO FITTING CONFIG"
        return (f"{head}; chose {self.best.candidate.label()} "
                f"(predicted {self.best.prediction.gb:.2f} GB)")


class NoFittingConfig(RuntimeError):
    """Every candidate's predicted waterline exceeds the budget."""

    def __init__(self, plan: Plan):
        self.plan = plan
        tight = min(plan.rows, key=lambda r: r.prediction.gb) \
            if plan.rows else None
        msg = f"no candidate fits {plan.budget_gb:.2f} GB"
        if tight is not None:
            msg += (f"; smallest is {tight.candidate.label()} at "
                    f"{tight.prediction.gb:.2f} GB — shrink the batch "
                    f"or raise --hbm-budget-gb")
        super().__init__(msg)


def enumerate_candidates(*, per_device_batch: int,
                         remat=REMAT_POLICIES,
                         accum=(1, 2, 4),
                         quant=QUANT_CHOICES,
                         state=STATE_CHOICES,
                         offload=OFFLOAD_CHOICES) -> list[Candidate]:
    """The cross product, pruned to accum splits that divide the
    per-device batch (the step factory's own requirement)."""
    out = []
    for r in remat:
        for a in accum:
            if a < 1 or (per_device_batch % a):
                continue
            for q in quant:
                for s in state:
                    for o in offload:
                        if o == "opt_act" and r not in ("save_attn",
                                                        "save_dots_q8"):
                            continue  # needs a named-save remat policy
                        out.append(Candidate(r, a, q, s, o))
    return out


def modeled_speed(c: Candidate, prior: dict | None = None) -> float:
    """Relative throughput of one candidate.  A measured prior row (same
    remat/quant/state knobs, any batch) anchors the score directly via
    its TFLOPS; the calibrated multiplier model covers the rest of the
    space.  Offload and accumulation never appear in bench row names, so
    their multipliers apply on top of an anchored score too — otherwise
    an offloaded twin would tie its no-offload prior and win on the
    waterline tie-break despite the PCIe cost."""
    accum = 1.0 + _ACCUM_OVERHEAD * (c.accum_steps - 1)
    residual = _OFFLOAD_SPEED.get(c.offload, 1.0) / accum
    if prior and prior.get("tflops_per_device"):
        return float(prior["tflops_per_device"]) * residual
    speed = (_REMAT_SPEED.get(c.remat_policy, 1.0)
             * _QUANT_SPEED.get(c.matmul_precision, 1.0)
             * _STATE_SPEED.get(c.state_precision, 1.0))
    return speed * residual


# ---------------------------------------------------------- bench priors

# bench row names: explicit[_reshard|_noreshard][_save_*]
# [_int8(_bwd)|_fp8(_delayed|_pallas)][_s8][_b{N}x][_mesh{D}x{F}x{T}] —
# parsed back into candidate knobs so measured rows can anchor the
# planner's throughput model.
_NAME_BSCALE = re.compile(r"_b(\d+)x$")
_NAME_MESH = re.compile(r"_mesh(\d+(?:x\d+){2,3})")


def parse_bench_config_name(name: str) -> dict | None:
    """Knob dict for one bench matrix row name, or None for rows that are
    not explicit-FSDP knob points (auto variant, sync-step A/B, ring)."""
    if not name.startswith("explicit"):
        return None
    if any(t in name for t in ("syncstep", "ring", "noreshard")):
        return None
    rest = name.removeprefix("explicit").removeprefix("_reshard")
    # mesh token first: it trails the name, and the batch-scale regex
    # is end-anchored
    mesh_shape = None
    mm = _NAME_MESH.search(rest)
    if mm:
        mesh_shape = tuple(int(s) for s in mm.group(1).split("x"))
        rest = rest[:mm.start()] + rest[mm.end():]
    m = _NAME_BSCALE.search(rest)
    bscale = int(m.group(1)) if m else 1
    if m:
        rest = rest[:m.start()]
    knobs = {"remat_policy": "full", "matmul_precision": "bf16",
             "state_precision": "full", "batch_scale": bscale}
    if mesh_shape is not None:
        # only mesh rows carry the key, so legacy names parse to the
        # exact dict shape they always did; read with .get()
        knobs["mesh_shape"] = mesh_shape
    if "_s8" in rest:
        knobs["state_precision"] = "int8"
        rest = rest.replace("_s8", "")
    if "_int8" in rest:
        knobs["matmul_precision"] = "int8_bwd"
        rest = rest.replace("_int8_bwd", "").replace("_int8", "")
    elif "_fp8" in rest:
        # longest token first so "fp8" never eats its variants' suffixes
        for tok in ("fp8_delayed", "fp8_pallas", "fp8"):
            if f"_{tok}" in rest:
                knobs["matmul_precision"] = tok
                rest = rest.replace(f"_{tok}", "")
                break
    rest = rest.strip("_")
    if rest:
        if rest not in REMAT_POLICIES:
            return None
        knobs["remat_policy"] = rest
    return knobs


def load_bench_priors(paths=None) -> list[dict]:
    """Measured matrix rows from the bench JSON files the caller names
    (none named = none loaded), each annotated with its parsed knobs —
    the planner's step-time priors."""
    rows = []
    from ..telemetry.report import load_baseline_rows
    for p in paths or ():
        try:
            loaded = load_baseline_rows(str(p))
        except Exception:  # noqa: BLE001 - priors are best-effort
            continue
        for r in loaded:
            name = r.get("config")
            if not name or r.get("error"):
                continue
            knobs = parse_bench_config_name(str(name))
            if knobs and r.get("tflops_per_device"):
                rows.append({**r, "knobs": knobs})
    return rows


def _find_prior(c: Candidate, priors, per_device_batch: int,
                base_batch: int | None = None) -> dict | None:
    """Latest measured row with this candidate's exact knobs; prefers a
    matching batch scale when ``base_batch`` is known."""
    want_mesh = getattr(c, "mesh_shape", None)
    hits = [p for p in priors or [] if p["knobs"]["remat_policy"]
            == c.remat_policy
            and p["knobs"]["matmul_precision"] == c.matmul_precision
            and p["knobs"]["state_precision"] == c.state_precision
            and (tuple(p["knobs"]["mesh_shape"])
                 if p["knobs"].get("mesh_shape") else None) == want_mesh]
    if not hits:
        return None
    if base_batch:
        exact = [p for p in hits
                 if p["knobs"]["batch_scale"] * base_batch
                 == per_device_batch]
        if exact:
            hits = exact
    return hits[-1]


# ---------------------------------------------------------------- plan()

def plan(cfg, *, batch: int, seq: int, ws: int = 1,
         hbm_budget_gb: float | None = None, candidates=None,
         priors=None, prior_base_batch: int | None = None,
         verify=None) -> Plan:
    """Rank the knob space for ``cfg`` at global ``batch`` × ``seq`` over
    ``ws`` devices and pick the best predicted-fitting candidate.

    Every candidate is costed with the analytic predictor only — a
    candidate over ``hbm_budget_gb`` is rejected *pre-compile* with its
    predicted waterline attached.  ``verify(candidate) -> step, args``
    optionally re-checks the winner compile-side (demoting it and
    promoting the runner-up on a compiler OOM).  Raises
    :class:`NoFittingConfig` when nothing fits."""
    pdb = max(batch // ws, 1)
    if candidates is None:
        candidates = enumerate_candidates(per_device_batch=pdb)
    rows = []
    for c in candidates:
        pred = analytic_waterline(
            c.apply_to(cfg), batch=batch, seq=seq, ws=ws,
            accum_steps=c.accum_steps, state_precision=c.state_precision,
            offload=c.offload, capacity_gb=hbm_budget_gb)
        fits = pred.fits if pred.fits is not None else True
        prior = _find_prior(c, priors, pdb, prior_base_batch)
        row = PlannedCandidate(c, pred, fits, modeled_speed(c, prior),
                               prior)
        if prior:
            # prior-anchored score IS TFLOPS/device: convert to an
            # absolute step-time estimate via the analytic FLOPs model
            from ..utils.flops import get_model_flops_per_token
            ft = get_model_flops_per_token(c.apply_to(cfg), seq)
            row.est_step_ms = (batch * seq * ft
                               / (row.score * 1e12 * ws) * 1e3)
        rows.append(row)
    rows.sort(key=lambda r: (-r.fits, -r.score, r.prediction.gb))
    fitting = [r for r in rows if r.fits]
    result = Plan(best=None, rows=rows, budget_gb=hbm_budget_gb)
    while fitting:
        head = fitting[0]
        if verify is None:
            result.best = head
            return result
        from .predictor import predict_from_step
        step, args = verify(head.candidate)
        compiled = predict_from_step(step, *args,
                                     capacity_gb=hbm_budget_gb)
        head.prediction = compiled
        if compiled.fits is not False:
            result.best = head
            return result
        head.fits = False           # compiler overruled the analytic fit
        fitting.pop(0)
    raise NoFittingConfig(result)
