"""Stage orchestration: enumerate → prune → rank → plan.

``tune()`` is the subsystem's one programmatic entry point.  The
throughput objective compiles nothing: it ranks, and the plan's chosen
candidate is the predicted best.  The serving objective runs its
``top_k`` pool-knob candidates through the engine.
"""

from __future__ import annotations

import time

from ..memory_plan.predictor import analytic_waterline
from .cost import TunerCostModel, _planner_candidate
from .knobs import KnobSpace, ServingKnobSpace
from .plan import PLAN_SCHEMA


def _candidate_mesh_plan(c):
    """The MeshPlan a candidate's ``mesh_shape`` names, or None for the
    flat-dp fsdp family (lazy import: the composable module pulls the
    jax-side step machinery)."""
    shape = getattr(c, "mesh_shape", None)
    if not shape:
        return None
    from ..parallel.composable import MeshPlan
    dp, fsdp, tp, sp = (tuple(shape) + (1, 1, 1, 1))[:4]
    return MeshPlan(dp=dp, fsdp=fsdp, tp=tp, sp=sp)


def prune_candidates(cands, cfg, *, base_batch: int, seq: int, ws: int,
                     capacity_gb: float | None):
    """Stage 2: analytic waterline per candidate, pre-compile.  Returns
    ``(survivors, pruned_rows)``; every rejected candidate is reported
    with its predicted GB (never silently dropped).  With no capacity
    (CPU sim exposes none and no budget was given) nothing prunes, but
    the predictions still ride along."""
    survivors, pruned = [], []
    preds = {}
    for c in cands:
        pc = _planner_candidate(c)
        batch = base_batch * c.batch_scale * ws
        pred = analytic_waterline(
            pc.apply_to(cfg), batch=batch, seq=seq, ws=ws,
            accum_steps=c.accum_steps, state_precision=c.state_precision,
            offload=c.offload, capacity_gb=capacity_gb,
            mesh_plan=_candidate_mesh_plan(c))
        preds[c] = round(pred.gb, 3)
        if pred.fits is False:
            pruned.append({"config": c.bench_name(),
                           "predicted_gb": round(pred.gb, 3),
                           "capacity_gb": round(capacity_gb, 2)})
        else:
            survivors.append(c)
    return survivors, pruned, preds


def tune(model_name: str, seq: int, base_batch: int, *,
         objective: str = "throughput", space=None,
         budget_gb: float | None = None, top_k: int = 5,
         cost_model_path: str | None = None,
         prior_paths: list | None = None,
         cost: TunerCostModel | None = None, log=None) -> dict:
    """Run the stages and return the plan document (the caller decides
    whether to ``save_plan`` it).  ``objective="throughput"`` enumerates,
    prunes and ranks without a compile, and the chosen candidate is the
    predicted argmax (``"measured": None``); ``top_k`` belongs to
    ``objective="p99_latency"``, which measures that many pool-knob
    candidates.  ``base_batch`` is the per-device batch at scale 1;
    global batch for a candidate is ``base_batch × batch_scale × ws``."""
    import jax
    log = log or (lambda *a: None)
    if objective == "p99_latency":
        return _tune_serving(space, top_k=top_k, log=log)
    if objective != "throughput":
        raise ValueError(f"unknown objective {objective!r}")

    from ..models import transformer as T
    from ..utils.memory import hbm_capacity_gb
    cfg = getattr(T, model_name)
    ws = len(jax.devices())
    space = space or KnobSpace()
    if cost is None:
        cost = TunerCostModel.from_artifacts(
            cost_model_path=cost_model_path, prior_paths=prior_paths)

    # 1. enumerate — mesh-shape feasibility (axis product == devices,
    # tp | heads, sp | seq) prunes right here, before any pricing
    cands = space.enumerate(
        base_batch, n_devices=ws, n_heads=cfg.num_attention_heads,
        n_kv_heads=getattr(cfg, "num_key_value_heads", None),
        seq_len=seq)
    log(f"[tune] stage 1: {len(cands)} candidates from the knob space")

    # 2. prune
    capacity = budget_gb if budget_gb is not None else hbm_capacity_gb()
    survivors, pruned, preds = prune_candidates(
        cands, cfg, base_batch=base_batch, seq=seq, ws=ws,
        capacity_gb=capacity)
    log(f"[tune] stage 2: {len(pruned)} pruned analytically "
        f"(capacity {capacity} GB), {len(survivors)} survive")

    # 3. rank
    ranked = cost.rank(survivors, cfg, seq=seq, base_batch=base_batch,
                       ws=ws)
    ranking_rows = [{**pred, "predicted_gb": preds[c],
                     "knobs": c.to_dict()} for c, pred in ranked]
    log(f"[tune] stage 3: ranked {len(ranked)} "
        f"(top: {ranking_rows[0]['config'] if ranking_rows else '-'})")

    if ranking_rows:
        top = ranking_rows[0]
        chosen = {"config": top["config"], "knobs": top["knobs"],
                  "predicted": {k: top[k] for k in top
                                if k not in ("knobs",)},
                  "measured": None}
    else:
        chosen = None

    return {
        "schema_version": PLAN_SCHEMA,
        "objective": objective,
        "model": model_name, "seq": seq, "base_batch": base_batch,
        "devices": ws, "platform": jax.devices()[0].platform,
        "knob_space": space.axes(),
        "knob_space_hash": space.space_hash(),
        "cost_model_hash": cost.hash(),
        "priors_hash": cost.priors_hash(),
        "provenance": {"cost_model_path": cost.cost_model_path,
                       "prior_paths": cost.prior_paths},
        "budget_gb": capacity,
        "enumerated": len(cands),
        "pruned": pruned,
        "ranking": ranking_rows,
        # nothing is measured or compiled for this objective; the keys
        # stay so that plans written before and after read alike
        "measured": [],
        "compiles_spent": 0,
        "chosen": chosen,
    }


# ------------------------------------------------------------- serving

def _serving_proxy(k: dict) -> float:
    """Heuristic pre-measurement ordering for pool knobs (measurement
    decides among the top-k; this only picks WHICH k to measure): more
    decode slots amortize the per-step scheduler overhead, bigger
    prefill chunks cut TTFT chunking stalls, tighter sync cadence costs
    host round-trips.  Speculation is priced as a mild bonus that grows
    with k but is taxed by draft depth (k draft forwards ride every
    verify) — measurement owns the real acceptance-rate question."""
    spec = 0.0
    if k.get("spec_k"):
        spec = (0.4 * k["spec_k"]
                - 0.2 * k["spec_k"] * k.get("draft_layers", 1))
    return (k["max_batch"] * 1.0 + k["prefill_chunk"] / 32.0
            - 4.0 / max(k["sync_every"], 1) - k["page_size"] / 64.0
            + spec)


def _measure_serving_knobs(knobs: dict, n_requests: int = 16) -> dict:
    """Closed seeded burst through the real ServingEngine — the p99
    objective's measuring stage."""
    import numpy as np
    import jax
    from ..models import transformer as T
    from ..serving import ServingEngine
    cfg = T.TINY_LM
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    eng = ServingEngine(params, cfg, max_seq_len=64, **knobs)
    for _ in range(n_requests):
        plen = int(rng.integers(4, 25))
        prompt = rng.integers(1, cfg.vocab_size,
                              size=plen).astype("int32")
        eng.submit(prompt, max_new_tokens=int(rng.integers(4, 13)))
    t0 = time.perf_counter()
    eng.run()
    wall_ms = (time.perf_counter() - t0) * 1e3
    slo = eng.slo_report()
    return {"wall_ms": round(wall_ms, 1),
            "p99_ttft_ms": slo.get("ttft_ms", {}).get("p99"),
            "p99_per_token_ms": slo.get("per_token_ms", {}).get("p99"),
            "tokens_per_s": slo.get("tokens_per_s")}


def _tune_serving(space, *, top_k: int, log) -> dict:
    import jax
    space = space or ServingKnobSpace()
    cands = space.enumerate()
    log(f"[tune] serving: {len(cands)} pool-knob candidates")
    ranked = sorted(cands, key=_serving_proxy, reverse=True)
    measured = []
    for k in ranked[:max(top_k, 1)]:
        try:
            row = _measure_serving_knobs(k)
        except Exception as e:  # noqa: BLE001 - a row must not kill the plan
            row = {"error": f"{type(e).__name__}: {str(e)[:200]}"}
        measured.append({"knobs": k, **row})
        log(f"[tune] serving {k} -> "
            f"p99/token {row.get('p99_per_token_ms', row.get('error'))}")
    good = [m for m in measured
            if "error" not in m and m.get("p99_per_token_ms")]
    chosen = None
    if good:
        best = min(good, key=lambda m: m["p99_per_token_ms"])
        chosen = {"config": "serving_pool", "knobs": best["knobs"],
                  "predicted": {"proxy": _serving_proxy(best["knobs"])},
                  "measured": {k: best[k] for k in
                               ("p99_ttft_ms", "p99_per_token_ms",
                                "tokens_per_s")}}
    return {
        "schema_version": PLAN_SCHEMA,
        "objective": "p99_latency",
        "model": "TINY_LM", "devices": len(jax.devices()),
        "platform": jax.devices()[0].platform,
        "knob_space": space.axes(),
        "knob_space_hash": space.space_hash(),
        "cost_model_hash": "serving_proxy_v1",
        "enumerated": len(cands),
        "pruned": [],
        "ranking": [{"knobs": k,
                     "proxy": round(_serving_proxy(k), 3)}
                    for k in ranked],
        "measured": measured,
        "compiles_spent": len(measured),
        "chosen": chosen,
    }
