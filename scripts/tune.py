"""Autotuner entry point.

    python scripts/tune.py --model TINY_LM --seq 256 --batch 1 \
        --out plan.json
    python scripts/tune.py --check plan.json
    dts-launch tune --model TINY_LM ...

Stages (``distributed_training_sandbox_tpu/tuner``): enumerate the knob
space, prune over-HBM candidates analytically (predicted GB per
rejection), rank survivors via the bench priors ``--priors`` names + the
run-registry ledger cost model, and emit a versioned ``plan.json`` the
drivers replay via ``--plan``.  The throughput objective compiles and
measures nothing; ``--objective p99_latency`` measures its ``--top-k``
pool-knob candidates through the engine.

``--check PLAN`` is the staleness gate: exit 0 when the plan's
knob-space and cost-model provenance hashes still match what today's
code + the files the plan names would re-derive, 1 when stale, 2 when
unreadable.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def _check(path: str) -> int:
    from distributed_training_sandbox_tpu.tuner import (check_plan,
                                                        load_plan)
    try:
        doc = load_plan(path)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"[tune] --check {path}: UNREADABLE ({e})",
              file=sys.stderr)
        return 2
    verdict = check_plan(doc)
    if verdict["stale"]:
        print(f"[tune] --check {path}: STALE")
        for r in verdict["reasons"]:
            print(f"  - {r}")
        print("  re-run scripts/tune.py and commit the fresh plan")
        return 1
    print(f"[tune] --check {path}: ok (knob space "
          f"{verdict['knob_space_hash']}, cost model "
          f"{verdict['cost_model_hash']})")
    return 0


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        description="autotuner: enumerate / prune / rank -> plan.json")
    p.add_argument("--model", type=str, default="TINY_LM",
                   help="TransformerConfig name (default TINY_LM)")
    p.add_argument("--seq", type=int, default=256)
    p.add_argument("--batch", type=int, default=1,
                   help="per-device batch at scale 1 (global batch per "
                        "candidate = batch x batch_scale x devices)")
    p.add_argument("--objective", type=str, default="throughput",
                   choices=("throughput", "p99_latency"))
    p.add_argument("--budget-gb", type=float, default=None,
                   help="HBM budget for analytic pruning (default: the "
                        "device's own capacity when exposed)")
    p.add_argument("--top-k", type=int, default=5,
                   help="pool-knob candidates --objective p99_latency "
                        "measures (the throughput objective ranks and "
                        "measures none)")
    p.add_argument("--cost-model", type=str, default="cost_model.json",
                   help="run-registry export (scripts/runs.py "
                        "export-cost-model); missing file = "
                        "compute-only ranking")
    p.add_argument("--priors", type=str, nargs="*", default=None,
                   help="bench prior JSONs (default: none, the "
                        "relative-speed multipliers alone)")
    p.add_argument("--out", type=str, default="plan.json")
    p.add_argument("--check", type=str, default=None, metavar="PLAN",
                   help="staleness-gate mode: validate a plan "
                        "against current hashes and exit")
    p.add_argument("--cpu-devices", type=int, default=None,
                   help="force N simulated CPU devices before the "
                        "backend initializes")
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if args.check:
        return _check(args.check)
    if args.cpu_devices:
        from distributed_training_sandbox_tpu.utils import use_cpu_devices
        use_cpu_devices(args.cpu_devices)
    from distributed_training_sandbox_tpu.tuner import save_plan, tune

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    doc = tune(args.model, args.seq, args.batch,
               objective=args.objective, budget_gb=args.budget_gb,
               top_k=args.top_k, cost_model_path=args.cost_model,
               prior_paths=args.priors, log=log)
    save_plan(doc, args.out)
    chosen = doc.get("chosen") or {}
    print(json.dumps({
        "plan": args.out, "objective": doc["objective"],
        "enumerated": doc["enumerated"], "pruned": len(doc["pruned"]),
        "measured": len(doc["measured"]),
        "compiles_spent": doc["compiles_spent"],
        "chosen": chosen.get("config"),
        "measured_numbers": chosen.get("measured"),
        "knob_space_hash": doc["knob_space_hash"],
        "cost_model_hash": doc["cost_model_hash"],
    }))
    return 0 if chosen else 1


if __name__ == "__main__":
    raise SystemExit(main())
