#!/usr/bin/env python3
"""Run one cell of the benchmark once, in this process.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python benchmarks/run.py --list
    python benchmarks/run.py --workload <cell> --rehearse-cpu

The last line of standard output is the one JSON object the contract asks
for.  ``--trace 0`` gives the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a short traced window.  Its last key, ``compared``,
holds each number ``correct`` was decided from beside its limit; the same
pairs are the last lines of standard error.  Without a TPU holding the
chips the cell asks for the program exits 2 and prints no result.

``--rehearse-cpu`` runs the cell's control flow and its reference check on
the CPU at the tiny sizes the data files give under ``rehearse``.  It
prints counts, never a metric and never a result line: a CPU run says
nothing about speed.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

T_ENTRY = time.perf_counter()
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmarks import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--probe", metavar="JSON", help=(
        "merge this object into the cell's files ({\"config\": {...}, "
        "\"traffic\": {...}}), run, and print only the correctness "
        "check's distances: how far a lower precision lands from the "
        "reference.  Never prints a result line."))
    args = ap.parse_args(argv)

    if args.list:
        for row in harness.list_cells():
            print(json.dumps(row))
        return 0
    if not args.workload:
        ap.error("--workload is required")
    cell = harness.load_cell(args.workload)
    seconds = args.seconds if args.seconds is not None \
        else float(harness.load_benchmark()["run_seconds"])
    if args.probe:
        over = json.loads(args.probe)
        harness.deep_merge(cell.config, over.get("config", {}))
        harness.deep_merge(cell.traffic, over.get("traffic", {}))

    harness.prepare_platform(cell.chips, args.rehearse_cpu)
    import jax
    import distributed_training_sandbox_tpu  # noqa: F401  (compile cache)
    if args.rehearse_cpu:
        device, peaks = None, None
    else:
        device = harness.assert_accelerator(cell.chips)
        if device is None:
            return harness.EXIT_NO_DEVICE
        peaks = harness.load_peaks(device["kind"])
    harness.OUT.mkdir(exist_ok=True)

    watch = harness.CompileWatch()
    phases = harness.Phases(T_ENTRY)
    phases.mark("imports")
    runner = harness.find_module("runners", cell.runner)
    ref = harness.find_module("reference", cell.architecture,
                              needs=runner.REFERENCE_EXPORTS)
    obs = runner.run(cell, ref=ref, seed=args.seed, seconds=seconds,
                     trace=bool(args.trace), rehearse=args.rehearse_cpu,
                     watch=watch, phases=phases)
    obs["check"]["reference"] = str(
        Path(ref.__file__).relative_to(harness.ROOT))
    phases.mark("after_window")
    print(f"[bench] phases: {phases}", file=sys.stderr)
    if args.probe:
        print("probe " + json.dumps({"override": json.loads(args.probe),
                                     "check": obs["check"]}, default=str))
        return 3

    if args.rehearse_cpu:
        print(f"rehearsal on {jax.default_backend()} x{cell.chips}: "
              f"cell={cell.name} attempted={obs['attempted']} "
              f"failed={obs['failed']} reference_ok={obs['check']['ok']} "
              f"compiles_in_window={obs['compiles_in_window']}")
        print(f"rehearsal check: {json.dumps(obs['check'], default=str)}")
        return 0 if obs["check"]["ok"] and not obs["failed"] else 1

    ctx = harness.Context(cell=cell, fields=obs["fields"],
                          counters=obs["counters"], peaks=peaks,
                          counts=harness.cell_counts(cell))
    device["memory_peak_bytes"] = harness.memory_peak_bytes(obs["devices"])
    breakdown = None
    if args.trace:
        from benchmarks import reduce_trace
        ctx.trace = reduce_trace.reduce(reduce_trace.load_xplane(
            reduce_trace.find_xplane(obs["trace_dir"])))
        device["busy_s"] = ctx.trace.busy_s
        device["window_s"] = ctx.trace.window_s
        breakdown = ctx.trace.breakdown(aliases=reduce_trace.alias_modules(
            ctx.trace, obs["counters"].get("program_launches", {})))
        metrics = harness.read_metrics(cell.per_layer, ctx)
    else:
        metrics = harness.read_metrics(cell.end_to_end, ctx)
    correct = obs["correct"] and obs["compiles_in_window"] == 0
    print(f"[bench] check: {json.dumps(obs['check'], default=str)}",
          file=sys.stderr)
    if obs["compiles_in_window"]:
        print(f"[bench] {obs['compiles_in_window']} compilation(s) inside "
              f"the measured window", file=sys.stderr)
    if not args.trace:
        setup = (time.perf_counter() - T_ENTRY) - obs["window_wall_s"]
        metrics["setup_s"] = (setup, "s")
    # each number ``correct`` was decided from beside its limit: the last
    # lines on standard error, and the last key of the result line
    compared = {**obs["check"].get("compared", {}),
                "failed": [obs["failed"], 0],
                "compiles_in_window": [obs["compiles_in_window"], 0]}
    for name, (number, *limit) in compared.items():
        print(f"[bench] compared {name}: {number!r} limit "
              f"{' to '.join(repr(x) for x in limit)}", file=sys.stderr)
    sys.stdout.flush()
    sys.stderr.flush()
    print(harness.result_line(
        correct=correct, attempted=obs["attempted"], failed=obs["failed"],
        metrics=metrics, device=device, breakdown=breakdown,
        compared=compared), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
