"""Autotuner: turn the telemetry the stack already produces —
waterline predictions (``memory_plan``), measured per-collective busbw
(``telemetry/ledger`` via the run-registry export), bench priors (JSON
files the caller names) — into the knobs a human used to pick by hand.

Stages behind one entry point (``scripts/tune.py`` /
``dts-launch tune``):

  1. **enumerate** — a declarative :class:`KnobSpace` over strategy ×
     batch × accum × remat × quantization × opt-state precision × host
     offload × overlap/sync knobs, deterministic under a fixed seed.
  2. **prune** — reject over-HBM candidates *pre-compile* via the
     analytic waterline model; every rejection is reported with its
     predicted GB.
  3. **rank** — price the survivors with :class:`TunerCostModel`:
     bench-prior-anchored TFLOPS where a measured row with the same
     knobs exists, the calibrated multiplier model otherwise, plus
     ledger-measured comm cost per (kind, payload bucket, axis) from
     the run-registry ``cost_model.json`` export.
  4. **plan** — emit a versioned, reproducible ``plan.json`` (chosen
     knobs + predicted numbers + provenance hashes of the cost model
     and knob space) that the drivers replay exactly via ``--plan``.
     The throughput objective compiles and measures nothing (its choice
     is the predicted best); the serving objective measures its top-k
     pool-knob candidates through the engine.
"""

from .knobs import KnobSpace, ServingKnobSpace, TunerCandidate
from .cost import TunerCostModel
from .plan import (PLAN_SCHEMA, apply_plan_to_train_config, check_plan,
                   load_plan, plan_cfg_overrides, plan_manifest_stamp,
                   plan_serving_knobs, plan_step_kwargs,
                   plan_train_overrides, save_plan)
from .search import tune
from .simrank import (PRERANK_SCHEMA, load_prerank, sim_rank_serving,
                      write_prerank)

__all__ = [
    "KnobSpace", "ServingKnobSpace", "TunerCandidate", "TunerCostModel",
    "PLAN_SCHEMA", "apply_plan_to_train_config", "check_plan",
    "load_plan", "save_plan", "plan_cfg_overrides", "plan_serving_knobs",
    "plan_step_kwargs", "plan_train_overrides", "plan_manifest_stamp",
    "tune",
    "PRERANK_SCHEMA", "load_prerank", "sim_rank_serving",
    "write_prerank",
]
