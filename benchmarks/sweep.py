#!/usr/bin/env python3
"""Several windows of one serving cell on one warm engine, one JSON line
each: to find a cell's knee, once, when the cell is defined, and to see how
far its metrics spread from seed to seed before paying for whole runs.  The
benchmark's own runs never search.

    python benchmarks/sweep.py --workload serve-chat --rates 4,5,6,7,8 \\
        --seconds 20 --spread-at 0.8 --seeds 1,2,3,4,5,6

The knee is the highest rate, with every lower rate, at which at least 90%
of the requests due in the window have a first token within the limit (the
traffic file's ``slo``) and the queue at the window's end is no deeper than
at its middle, in the window of every ``--knee-seeds`` seed: where the
bursts fall decides a single window.  With ``--spread-at f`` the tool then
opens one window per ``--seeds`` seed at ``f`` x knee.  For a backlog cell
there is no rate: one window per seed.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from types import SimpleNamespace

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmarks import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--knee-seeds", default="1")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--ttft-limit-ms", type=float, default=None)
    ap.add_argument("--spread-at", type=float, default=0.0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    slo = cell.traffic.get("slo", {})
    limit_ms = args.ttft_limit_ms if args.ttft_limit_ms is not None \
        else float(slo.get("ttft_ms", 1000.0))
    min_share = float(slo.get("min_share", 0.9))
    harness.prepare_platform(cell.chips, args.rehearse_cpu)
    import distributed_training_sandbox_tpu  # noqa: F401
    if not args.rehearse_cpu \
            and harness.assert_accelerator(cell.chips) is None:
        return harness.EXIT_NO_DEVICE
    serve = harness.find_module("runners", "serve")
    readers = {n: harness.find_module("end_to_end", n) for n in (
        "serve_ttft_p90_ms", "serve_tpot_p50_ms", "serve_tokens_per_s")}
    st = serve.setup(cell, 0, args.rehearse_cpu, harness.spans(False))
    base = st["params_t"]
    seeds = [int(s) for s in args.seeds.split(",")]
    drain = float(cell.traffic["drain_s"])

    def one(rate, seed) -> dict:
        if rate is not None:
            st["params_t"] = {**base, "arrival": {**base["arrival"],
                                                  "rate_per_s": rate}}
        tr = st["traffic"].generate(st["params_t"], seed,
                                    st["mcfg"].vocab_size, args.seconds)
        c = serve.window(st, tr, args.seconds, drain)["counters"]
        st["engine"].release_all()      # what a backlog left in the queue
        ctx = SimpleNamespace(counters=c)
        t = readers["serve_ttft_p90_ms"].ttfts_ms(c)
        depth = c["queue_depth"]
        mid = max([d for ts, d in depth if ts <= args.seconds / 2][-3:],
                  default=0)
        end = max([d for _, d in depth][-3:], default=0)
        ok = sum(x <= limit_ms for x in t) / max(len(t), 1)
        s = c["stats"]
        row = {"rate": rate, "seed": seed, "requests": len(t),
               "ttft_ok_share": ok, "queue_mid": mid, "queue_end": end,
               "sustained": bool(ok >= min_share and end <= mid),
               "rounds": s["rounds"]}
        if not args.rehearse_cpu:
            # a CPU run never prints a time or a rate
            row.update({n: r.read(ctx) for n, r in readers.items()})
            row.update({f"ttft_p{q}_ms": harness.percentile(t, q)
                        for q in (50, 75, 90, 95)})
            row["ttft_mean_ms"] = sum(t) / max(len(t), 1)
            row.update({
                # host clock around asynchronous dispatch: what the loop
                # waited for in a burst, not the decode program's time
                "decode_host_wait_ms_per_step":
                    1e3 * s["decode_s"] / max(s["decode_steps"], 1),
                "occupied_slots": s["occupancy_sum"] / max(s["rounds"], 1),
                "late_p99_ms": 1e3 * harness.percentile(c["lateness_s"], 99)
                if c["lateness_s"] else None})
        print(json.dumps(row), flush=True)
        return row

    if base["arrival"]["process"] == "backlog":
        for seed in seeds:
            one(None, seed)
    else:
        rates = [float(r) for r in args.rates.split(",") if r]
        knee = None
        for rate in rates:
            rows = [one(rate, int(k)) for k in args.knee_seeds.split(",")]
            if not all(r["sustained"] for r in rows):
                break
            knee = rate
        if rates:
            print(json.dumps({"knee": knee}), flush=True)
        at = args.spread_at * knee if (args.spread_at and knee) \
            else float(base["arrival"]["rate_per_s"])
        if args.spread_at or not rates:
            for seed in seeds:
                one(round(at, 2), seed)
    st["engine"].close_pump()
    return 0


if __name__ == "__main__":
    sys.exit(main())
