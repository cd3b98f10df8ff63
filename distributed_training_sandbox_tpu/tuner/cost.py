"""Stage-3 cost model: price every surviving candidate before anyone
compiles it.

Two ingredient streams, both already produced by the stack:

  * **bench priors** (``memory_plan.planner.load_bench_priors``): a
    measured matrix row with the same (remat, quant, state) knobs anchors
    a candidate's TFLOPS directly; the multiplier model covers the
    unmeasured rest of the space, scaled by the best measured baseline
    row so anchored and unanchored scores are the same unit.
  * **run-registry cost model** (``scripts/runs.py export-cost-model``):
    ledger-measured bus bandwidth per (collective kind, payload bucket,
    mesh axis), loaded through the registry's own schema-validated
    :class:`CostModel` so a drifted export fails loudly here instead of
    mis-ranking silently.  It prices the FSDP choreography's per-step
    comm (two param all-gathers + one grad reduce-scatter on the dp
    axis); with no cost model, or on a 1-device mesh, comm is 0 and the
    ordering is compute-only.
"""

from __future__ import annotations

import hashlib
import importlib.util
import json
from pathlib import Path

from ..memory_plan.planner import (_ACCUM_OVERHEAD, _OFFLOAD_SPEED,
                                   _QUANT_SPEED, _REMAT_SPEED,
                                   _STATE_SPEED, Candidate, _find_prior,
                                   load_bench_priors, modeled_speed)

_REPO = Path(__file__).resolve().parents[2]


def _registry_mod():
    """Import ``scripts/runs.py`` (the run registry is a script, not a
    package module) under a stable name."""
    spec = importlib.util.spec_from_file_location(
        "_dts_runs", _REPO / "scripts" / "runs.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _planner_candidate(c) -> Candidate:
    """The memory-planner projection of a tuner candidate (the knobs the
    planner's prior-matching and multiplier model know about)."""
    return Candidate(remat_policy=c.remat_policy,
                     accum_steps=c.accum_steps,
                     matmul_precision=c.matmul_precision,
                     state_precision=c.state_precision,
                     offload=c.offload)


def _digest(blob: bytes) -> str:
    return hashlib.sha256(blob).hexdigest()[:16]


class TunerCostModel:
    """Assembled pricing for stage 3; see module docstring."""

    def __init__(self, *, cost_model=None, priors: list | None = None,
                 prior_paths: list | None = None,
                 cost_model_path: str | None = None):
        self.cost_model = cost_model
        self.priors = priors or []
        self.prior_paths = [str(p) for p in (prior_paths or [])]
        self.cost_model_path = cost_model_path
        # baseline anchor: the best measured full/bf16/full row converts
        # the multiplier model's relative scores into TFLOPS
        base = [p for p in self.priors
                if p["knobs"]["remat_policy"] == "full"
                and p["knobs"]["matmul_precision"] == "bf16"
                and p["knobs"]["state_precision"] == "full"]
        self.baseline_tflops = max(
            (float(p["tflops_per_device"]) for p in base), default=None)

    @classmethod
    def from_artifacts(cls, *, cost_model_path: str | None = None,
                       prior_paths: list | None = None
                       ) -> "TunerCostModel":
        """Load from the files the caller names: bench-prior JSONs
        (none named = no priors, the multiplier model alone) and, when
        present, the registry's ``cost_model.json``.
        A cost model that exists but fails schema validation raises —
        drift must not silently degrade to compute-only ranking."""
        cm = None
        if cost_model_path and Path(cost_model_path).is_file():
            cm = _registry_mod().load_cost_model(str(cost_model_path))
        priors = load_bench_priors(prior_paths)
        return cls(cost_model=cm, priors=priors,
                   prior_paths=prior_paths, cost_model_path=cost_model_path)

    # ---------------------------------------------------------- hashes
    def priors_hash(self) -> str:
        """Digest over the prior artifacts' bytes (sorted by path) —
        part of a plan's provenance."""
        h = hashlib.sha256()
        for p in sorted(self.prior_paths):
            try:
                h.update(Path(p).read_bytes())
            except OSError:
                h.update(f"missing:{p}".encode())
        return h.hexdigest()[:16]

    def hash(self) -> str:
        """Digest over everything that shapes the ordering: the cost
        model doc + the priors."""
        cm_blob = json.dumps(
            self.cost_model.doc if self.cost_model else None,
            sort_keys=True, default=str).encode()
        return _digest(cm_blob + self.priors_hash().encode())

    # --------------------------------------------------------- pricing
    def comm_us(self, cfg, ws: int, axis: str = "dp") -> float | None:
        """Ledger-priced per-step FSDP comm: forward param all-gather,
        backward re-gather (reshard_after_forward), grad reduce-scatter.
        None when the cost model has no matching (kind, bucket, axis)
        entry (reported, never silently zero)."""
        if self.cost_model is None or ws <= 1:
            return 0.0
        import jax.numpy as jnp
        nbytes = int(cfg.param_count()
                     * jnp.dtype(getattr(cfg, "dtype", "bfloat16")).itemsize)
        total, missing = 0.0, False
        for kind in ("all_gather", "all_gather", "reduce_scatter"):
            us = self.cost_model.estimate_us(kind, nbytes, axis)
            if us is None:
                missing = True
            else:
                total += us
        return None if missing else total

    def _closest_prior(self, pc: Candidate, pdb: int,
                       base_batch: int | None):
        """The measured rows that anchor ``pc``: the exact (remat,
        quant, state) match when one exists (the planner's own
        semantics), else EVERY row at the minimal knob distance — the
        caller extrapolates from each and keeps the most pessimistic,
        so a measured contradiction (save_dots×int8 measured SLOWER
        than the multipliers claim) overrides a sibling
        anchor's optimistic extrapolation.  A pure multiplier model
        makes exactly that mistake: it ranks unmeasured crossings above
        the measured champion.  Returns ``(priors, knob_distance)``."""
        exact = _find_prior(pc, self.priors, pdb, base_batch)
        if exact is not None:
            return [exact], 0
        dists = []
        for p in self.priors:
            k = p["knobs"]
            dist = ((k["remat_policy"] != pc.remat_policy)
                    + (k["matmul_precision"] != pc.matmul_precision)
                    + (k["state_precision"] != pc.state_precision))
            dists.append((dist, p))
        if not dists:
            return [], None
        dmin = min(d for d, _ in dists)
        return [p for d, p in dists if d == dmin], dmin

    @staticmethod
    def _mult(remat: str, quant: str, state: str) -> float:
        return (_REMAT_SPEED.get(remat, 1.0)
                * _QUANT_SPEED.get(quant, 1.0)
                * _STATE_SPEED.get(state, 1.0))

    def predict(self, cand, cfg, *, batch: int, seq: int, ws: int,
                base_batch: int | None = None,
                axis: str = "dp") -> dict:
        """Predicted step time + throughput for one candidate at global
        ``batch`` × ``seq`` over ``ws`` devices.  ``base_batch`` is the
        per-device batch at scale 1 (prior rows are matched on it).

        Anchoring: the closest measured prior's TFLOPS, scaled by the
        calibrated multiplier RATIO between the candidate's knobs and
        the prior's (exact match → ratio 1), times the residual for the
        knobs bench rows never carry (offload, accumulation).  With no
        priors at all the score stays relative (multiplier product)."""
        from ..utils.flops import get_model_flops_per_token
        pc = _planner_candidate(cand)
        pdb = max(batch // ws, 1)
        anchors, dist = self._closest_prior(pc, pdb, base_batch)
        prior = None
        score = modeled_speed(pc, anchors[0] if dist == 0 else None)
        residual = (_OFFLOAD_SPEED.get(pc.offload, 1.0)
                    / (1.0 + _ACCUM_OVERHEAD * (pc.accum_steps - 1)))
        tflops = None
        if anchors:
            cand_mult = self._mult(pc.remat_policy, pc.matmul_precision,
                                   pc.state_precision)
            per_anchor = []
            for p in anchors:
                k = p["knobs"]
                ratio = cand_mult / self._mult(k["remat_policy"],
                                               k["matmul_precision"],
                                               k["state_precision"])
                per_anchor.append(
                    (float(p["tflops_per_device"]) * ratio * residual, p))
            tflops, prior = min(per_anchor, key=lambda t: t[0])
        elif self.baseline_tflops:
            tflops = self.baseline_tflops * score
        anchor_exact_batch = bool(
            prior is not None and base_batch is not None
            and prior["knobs"]["batch_scale"] * base_batch == pdb)
        row = {"config": cand.bench_name(),
               "anchor": (prior or {}).get("config"),
               "anchor_knob_distance": dist,
               "anchor_exact_batch": anchor_exact_batch,
               "relative_score": round(score, 4),
               "predicted_tflops": round(tflops, 2) if tflops else None,
               "predicted_step_ms": None, "compute_ms": None,
               "comm_ms": None}
        if tflops:
            cfg_c = pc.apply_to(cfg)
            ft = get_model_flops_per_token(cfg_c, seq)
            compute_ms = batch * seq * ft / (tflops * 1e12 * ws) * 1e3
            comm = self.comm_us(cfg_c, ws, axis)
            comm_ms = (comm or 0.0) / 1e3
            step_ms = compute_ms + comm_ms
            # tokens/s from the UNROUNDED step time: at tiny-model step
            # times the display rounding below is coarser than the
            # spread between candidates and would scramble the ordering
            row.update(
                compute_ms=round(compute_ms, 3),
                comm_ms=round(comm_ms, 3) if comm is not None else None,
                predicted_step_ms=round(step_ms, 3),
                predicted_tokens_per_sec=round(
                    batch * seq / (step_ms / 1e3), 1))
        return row

    def rank(self, cands, cfg, *, seq: int, base_batch: int, ws: int,
             axis: str = "dp") -> list[tuple]:
        """Stage-3 ordering: every candidate priced and sorted best
        first.  Throughput objective = predicted tokens/s (global batch
        tokens over predicted step time); candidates the model cannot
        price absolutely (no baseline anchor) sort by relative score
        below the priced ones."""
        rows = []
        for c in cands:
            batch = base_batch * c.batch_scale * ws
            pred = self.predict(c, cfg, batch=batch, seq=seq, ws=ws,
                                base_batch=base_batch, axis=axis)
            pred.setdefault("predicted_tokens_per_sec", None)
            rows.append((c, pred))
        rows.sort(key=lambda t: (
            -(t[1]["predicted_tokens_per_sec"] or 0.0),
            t[1]["anchor_knob_distance"] if
            t[1]["anchor_knob_distance"] is not None else 9,
            0 if t[1]["anchor_exact_batch"] else 1,
            -t[1]["relative_score"], t[0].bench_name()))
        return rows
