"""Serving benchmark: Poisson traffic through the continuous-batching
engine, with the latency-SLO report and two hard gates.

Drives ``serving.ServingEngine`` with a seeded open-loop trace —
exponential inter-arrivals at ``--rate``, bimodal prompt lengths (chat
short / document long), uniform ``max_new`` — and files the engine's
SLO report (p50/p99 TTFT, p50/p99 per-token latency, tokens/s/device,
pool utilization, scheduler overhead) under the run's ``summary.json``
``serving`` key, so ``scripts/report.py`` renders it next to the
training runs.  Per-request TTFT and per-burst latency stream into
``steps.jsonl`` as the run goes.

Exit is nonzero when either serving invariant breaks:
  * **recompiles**: any jit-cache growth after the first round — the
    static-shape contract (admit/evict over the whole trace must never
    retrace);
  * **parity** (``--check-parity N``): the first N finished requests'
    tokens must be BITWISE equal to one-shot ``generate`` of the same
    prompt at the engine's pinned ``cache_capacity``.

With ``--replicas N`` (N >= 2) the trace drives a ``serving.Fleet``
instead: N engine replicas on separate device slices behind SLO-driven
admission control, with failover (``--inject-fault kill_replica@N:k`` /
``hang_decode@N:k`` / ``slow_replica@N:ms``), deadline load shedding
(``--deadline-ms``, structured rejections; ``queue_full`` sheds
backpressure the Poisson driver by shifting later arrivals), and
zero-drop weight hot-swap (``--swap-at K`` [+ ``--swap-ckpt DIR``],
``corrupt_swap`` proves the torn-checkpoint fallback).  The fleet adds
a third hard gate: any DROPPED request — admitted but never completed,
through kills, hangs, and swaps — exits nonzero (shed requests are
rejections, not drops).

The decode-speed-frontier legs ride the same trace and gates:
``--prefix-cache`` (radix prefix reuse; pair with ``--tenants N
--overlap-frac F`` for the tenant-skewed trace whose requests share
system prompts), ``--spec-k K --draft-layers N`` (speculative decoding
via a truncated-target draft), ``--flash-prefill`` (batched prefill
through the Pallas flash kernel).  All three keep the parity gate —
temp-0 speculation is exact, and the flash kernel equals the gather
path to float32 summation order (the same tokens in float32).

    python scripts/serve_bench.py --requests 64 --rate 16 --tp 2
    python scripts/serve_bench.py --requests 8 --disaggregate
    python scripts/serve_bench.py --tenants 4 --overlap-frac 0.7 --prefix-cache
    python scripts/serve_bench.py --spec-k 3 --draft-layers 1
    python scripts/serve_bench.py --replicas 2 --inject-fault kill_replica@2:1
    python scripts/serve_bench.py --replicas 2 --rate 200 --deadline-ms 400
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def build_trace(rng, n_requests: int, rate: float, vocab: int,
                max_seq_len: int, *, tenants: int = 0,
                overlap_frac: float = 0.0, sys_len: int = 16):
    """(arrival_s, prompt, max_new) triples — moved VERBATIM to
    ``serving/traces.py`` so the virtual-clock simulator consumes the
    same seeded draw stream (byte-identical traces per seed, pinned by
    ``tests/test_sim.py``).  This thin delegate keeps the historical
    import site alive; the import is deferred so ``--cpu-devices``
    still configures XLA before any package import can init jax."""
    from distributed_training_sandbox_tpu.serving.traces import (
        build_trace as _shared_build_trace)
    return _shared_build_trace(rng, n_requests, rate, vocab,
                               max_seq_len, tenants=tenants,
                               overlap_frac=overlap_frac,
                               sys_len=sys_len)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="Poisson traffic through the serving runtime + SLO "
                    "report")
    p.add_argument("--model", default="TINY_LM")
    p.add_argument("--requests", type=int, default=64)
    p.add_argument("--rate", type=float, default=16.0,
                   help="mean arrival rate, requests/s (default 16)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--max-batch", type=int, default=4)
    p.add_argument("--page-size", type=int, default=8)
    p.add_argument("--max-seq-len", type=int, default=80)
    p.add_argument("--prefill-chunk", type=int, default=16)
    p.add_argument("--sync-every", type=int, default=4)
    p.add_argument("--tp", type=int, default=0,
                   help="tensor-parallel degree (0 = single program; "
                        "N shards heads over a dp × tp mesh)")
    p.add_argument("--kv-quant", action="store_true",
                   help="int8 paged KV pool (+f32 row scales)")
    p.add_argument("--disaggregate", action="store_true",
                   help="prefill/decode on separate device slices with "
                        "page-block KV handoff")
    p.add_argument("--prefix-cache", action="store_true",
                   help="radix-tree prefix caching over KV pages: "
                        "requests sharing a prompt prefix alias the "
                        "same pages; admission grants only the "
                        "non-cached suffix")
    p.add_argument("--spec-k", type=int, default=0,
                   help="speculative decoding: the draft proposes K "
                        "tokens per burst slot, the target verifies "
                        "them in one (B, K+1) step (0 = off)")
    p.add_argument("--draft-layers", type=int, default=1,
                   help="draft model depth for --spec-k: the target's "
                        "first N layers (truncated-target draft)")
    p.add_argument("--flash-prefill", action="store_true",
                   help="batched multi-request prefill through the "
                        "Pallas flash-attention kernel "
                        "(ops/flash_prefill.py)")
    p.add_argument("--tenants", type=int, default=0,
                   help="tenant-skewed trace: N tenants with fixed "
                        "shared system prompts (0 = plain bimodal "
                        "trace)")
    p.add_argument("--overlap-frac", type=float, default=0.6,
                   help="fraction of requests opening with a tenant's "
                        "shared system prompt (needs --tenants)")
    p.add_argument("--sys-len", type=int, default=16,
                   help="shared system-prompt length for --tenants")
    p.add_argument("--hbm-budget-gb", type=float, default=None,
                   help="cap the pool via the capacity planner "
                        "(serving.accounting.pool_capacity_pages)")
    p.add_argument("--check-parity", type=int, default=4, metavar="N",
                   help="verify the first N finished requests bitwise "
                        "against one-shot generate (0 disables)")
    p.add_argument("--profile", action="store_true",
                   help="own an XLA profiler session: comm/compute "
                        "split + the decode collective ledger "
                        "(collectives.json) land in the run dir")
    p.add_argument("--trace-dir", default="profiler_traces")
    p.add_argument("--export-timeline", action="store_true",
                   help="after the run, merge spans.jsonl + the owned "
                        "device trace into <run-dir>/timeline.json.gz "
                        "(scripts/export_timeline.py)")
    p.add_argument("--param-scale", type=float, default=3.0,
                   help="scale random init weights — ~3 makes greedy "
                        "trajectories chaotic, so the parity check "
                        "discriminates (1.0 = raw init, which settles "
                        "on a constant token)")
    p.add_argument("--cpu-devices", type=int, default=None,
                   help="simulate N CPU devices (the gloo-mode twin). "
                        "Default: the live backend for a single engine, "
                        "but the fleet path (--replicas > 1) self-"
                        "selects max(8, replicas) simulated devices — "
                        "a 1-chip host can't carve replica slices; "
                        "pass 0 to force the live backend")
    p.add_argument("--replicas", type=int, default=1,
                   help="serve through a Fleet of N engine replicas "
                        "(failover + admission control + hot-swap; "
                        "1 = single engine, the default)")
    p.add_argument("--inject-fault", default=None, metavar="SPEC",
                   help="serving fault: kill_replica@N:k / "
                        "hang_decode@N:k / slow_replica@N:ms / "
                        "corrupt_swap (needs --replicas >= 2)")
    p.add_argument("--deadline-ms", type=float, default=None,
                   help="per-request TTFT deadline; arrivals whose "
                        "modeled TTFT exceeds it are shed at submit "
                        "with a structured rejection")
    p.add_argument("--swap-at", type=int, default=None, metavar="K",
                   help="hot-swap weights after K completed requests "
                        "(zero-drop drain, one replica at a time)")
    p.add_argument("--swap-ckpt", default=None, metavar="DIR",
                   help="checkpoint directory for --swap-at (default: "
                        "save a seed+1 init to a temp dir)")
    p.add_argument("--metrics-port", type=int, default=None,
                   help="serve live Prometheus metrics on this port "
                        "for the run's duration (0 = ephemeral port, "
                        "printed at start; default off)")
    p.add_argument("--watchdog-timeout", type=float, default=5.0,
                   help="per-replica decode watchdog budget, seconds "
                        "(converts a wedged burst into failover)")
    p.add_argument("--max-queue", type=int, default=8,
                   help="admission bound on the modeled waiting line; "
                        "deeper arrivals are shed queue_full")
    p.add_argument("--burst-ms", type=float, default=50.0,
                   help="admission controller's per-burst latency "
                        "prior (EWMA-calibrated as bursts complete)")
    p.add_argument("--plan", default=None, metavar="PLAN_JSON",
                   help="replay a p99-objective tuner plan "
                        "(scripts/tune.py --objective p99_latency): "
                        "its pool knobs override --max-batch/"
                        "--page-size/--prefill-chunk/--sync-every")
    args = p.parse_args(argv)
    plan = None
    if args.plan:
        from distributed_training_sandbox_tpu.tuner import (
            load_plan, plan_serving_knobs)
        doc = load_plan(args.plan)
        if doc.get("objective") != "p99_latency":
            print(f"[serve] --plan {args.plan} has objective "
                  f"{doc.get('objective')!r}; serving replays "
                  f"p99_latency plans", file=sys.stderr)
            return 2
        knobs = plan_serving_knobs(doc)
        for k in ("max_batch", "page_size", "prefill_chunk",
                  "sync_every", "spec_k", "draft_layers"):
            if k in knobs:
                setattr(args, k, int(knobs[k]))
        plan = (doc, args.plan)
        print(f"[serve] replaying plan {args.plan}: {knobs}")
    # device selection must happen BEFORE the backend initializes (a
    # live backend ignores the override), hence flag-driven, not
    # count-driven: the fleet path defaults to the simulated mesh
    # because counting live devices would itself pin the backend
    cpu_n = args.cpu_devices
    if cpu_n is None and args.replicas > 1:
        cpu_n = max(8, args.replicas)
    if cpu_n:
        from distributed_training_sandbox_tpu.utils import use_cpu_devices
        use_cpu_devices(cpu_n)
    if args.replicas > 1:
        return _fleet_main(args)
    for flag, name in ((args.inject_fault, "--inject-fault"),
                       (args.deadline_ms, "--deadline-ms"),
                       (args.swap_at, "--swap-at")):
        if flag is not None:
            print(f"[serve] {name} needs --replicas >= 2",
                  file=sys.stderr)
            return 2

    import jax
    import numpy as np
    from distributed_training_sandbox_tpu.models import transformer as T
    from distributed_training_sandbox_tpu.models.generate import generate
    from distributed_training_sandbox_tpu.serving import ServingEngine
    from distributed_training_sandbox_tpu.telemetry import TelemetryRun
    from distributed_training_sandbox_tpu.utils import make_mesh

    cfg = getattr(T, args.model)
    key = jax.random.PRNGKey(args.seed)
    params = T.init_params(key, cfg)
    if args.param_scale != 1.0:
        params = jax.tree.map(
            lambda x: (x * args.param_scale).astype(x.dtype), params)

    mesh = None
    if args.tp > 1:
        n_dev = len(jax.devices())
        if n_dev % args.tp:
            print(f"[serve] {n_dev} devices not divisible by tp="
                  f"{args.tp}", file=sys.stderr)
            return 2
        mesh = make_mesh({"dp": n_dev // args.tp, "tp": args.tp},
                         register=False)

    rng = np.random.default_rng(args.seed)
    trace = build_trace(rng, args.requests, args.rate, cfg.vocab_size,
                        args.max_seq_len, tenants=args.tenants,
                        overlap_frac=args.overlap_frac,
                        sys_len=args.sys_len)

    run_cfg = {"num_steps": 0, "batch_size": args.max_batch,
               "sequence_length": args.max_seq_len, "seed": args.seed,
               "requests": args.requests, "rate": args.rate,
               "page_size": args.page_size, "tp": args.tp,
               "kv_quant": args.kv_quant,
               "disaggregate": args.disaggregate,
               "prefix_cache": args.prefix_cache,
               "spec_k": args.spec_k,
               "draft_layers": args.draft_layers if args.spec_k else None,
               "flash_prefill": args.flash_prefill,
               "tenants": args.tenants,
               "overlap_frac": args.overlap_frac if args.tenants else None}
    if plan is not None:
        from distributed_training_sandbox_tpu.tuner import (
            plan_manifest_stamp)
        run_cfg["tuner"] = plan_manifest_stamp(plan[0], plan[1])
    prof = None
    if args.profile:
        from distributed_training_sandbox_tpu.utils.profiling import (
            ProfileSchedule, Profiler)
        # serving has no fixed step count; trace a window early enough
        # to catch steady-state decode bursts
        prof = Profiler(trace_dir=args.trace_dir,
                        schedule=ProfileSchedule(skip_first=2, wait=1,
                                                 warmup=2, active=8))
    failures = []
    with TelemetryRun("serving", model=args.model, mesh=mesh,
                      config=run_cfg, profiler=prof,
                      metrics_port=args.metrics_port) as telem:
        if telem.metrics_server is not None:
            print(f"[serve] metrics: {telem.metrics_server.url}",
                  flush=True)
        eng = ServingEngine(
            params, cfg, mesh=mesh, max_batch=args.max_batch,
            page_size=args.page_size, max_seq_len=args.max_seq_len,
            prefill_chunk=args.prefill_chunk,
            sync_every=args.sync_every, kv_quant=args.kv_quant,
            hbm_budget_gb=args.hbm_budget_gb,
            disaggregate=args.disaggregate,
            prefix_cache=args.prefix_cache, spec_k=args.spec_k,
            draft_layers=args.draft_layers if args.spec_k else None,
            flash_prefill=args.flash_prefill, telem=telem)
        reqs = [eng.submit(prompt, max_new_tokens=new, arrival_s=t)
                for t, prompt, new in trace]
        eng.run()
        slo = eng.slo_report()
        print(f"[serve] {slo['completed']}/{slo['requests']} requests, "
              f"TTFT p50 {slo['ttft_ms']['p50']} ms p99 "
              f"{slo['ttft_ms']['p99']} ms, per-token p50 "
              f"{slo['per_token_ms']['p50']} ms, "
              f"{slo['tokens_per_s']} tok/s "
              f"({slo['tokens_per_s_per_device']}/device)", flush=True)
        if "prefix_cache" in slo:
            pc = slo["prefix_cache"]
            print(f"[serve] prefix cache: hit rate {pc['hit_rate']} "
                  f"({pc['hit_pages']}/{pc['lookup_pages']} pages), "
                  f"{pc['evictions']} evictions", flush=True)
        if "speculative" in slo:
            sp = slo["speculative"]
            print(f"[serve] speculative k={sp['k']}: acceptance "
                  f"{sp['acceptance_rate']} "
                  f"({sp['accepted']}/{sp['proposed']}), "
                  f"{slo['scheduler']['decode_steps_per_token']} "
                  f"decode steps/token", flush=True)

        retr = slo["recompiles_after_warmup"]
        if retr is None or retr > 0:
            failures.append(f"jit cache grew after warmup: {retr}")
        if slo["completed"] != args.requests:
            failures.append(f"only {slo['completed']}/{args.requests} "
                            f"requests completed")

        parity = []
        for req in reqs[:args.check_parity]:
            ref = np.asarray(generate(
                params, req.prompt[None], cfg,
                max_new_tokens=req.max_new_tokens,
                kv_quant=args.kv_quant,
                cache_capacity=eng.view_capacity))[0]
            got = np.asarray(req.tokens, np.int32)
            same = got.shape == ref.shape
            agree = got == ref if same else np.zeros(0, bool)
            # where the tokens part ways matters to a reader as much as
            # whether they do: a first-token miss is a wrong prefill, a
            # late one is two programs rounding a near-tie differently
            parity.append({"rid": req.rid, "tokens": int(ref.size),
                           "first_token_equal": bool(same and agree[0]),
                           "tokens_equal": int(agree.sum())})
            if not (same and agree.all()):
                failures.append(
                    f"rid {req.rid}: tokens diverge from one-shot "
                    f"generate (got {got.tolist()[:8]}..., ref "
                    f"{ref.tolist()[:8]}...)")
        if args.check_parity:
            print(f"[serve] parity vs generate: "
                  f"{min(args.check_parity, len(reqs))} request(s) "
                  f"{'OK' if not failures else 'CHECKED (see failures)'}",
                  flush=True)
        slo["parity"] = parity
        slo["parity_checked"] = min(args.check_parity, len(reqs))
        slo["failures"] = failures
        telem.finalize(serving=slo)

    if args.export_timeline and telem.run_dir:
        sys.path.insert(0, str(Path(__file__).resolve().parent))
        from export_timeline import main as export_main
        export_main([telem.run_dir])

    print(json.dumps(slo, indent=1))
    for f in failures:
        print(f"[serve] FAIL: {f}", file=sys.stderr, flush=True)
    return 1 if failures else 0


def _fleet_main(args) -> int:
    """The ``--replicas N`` path: drive the trace through a Fleet with
    admission control, optional fault injection and hot-swap, and gate
    on drops + retraces (+ parity when weights never change)."""
    import tempfile

    import jax
    import numpy as np
    from distributed_training_sandbox_tpu.models import transformer as T
    from distributed_training_sandbox_tpu.models.generate import generate
    from distributed_training_sandbox_tpu.serving import Fleet, Rejection
    from distributed_training_sandbox_tpu.telemetry import TelemetryRun

    if args.tp > 1 or args.disaggregate:
        print("[serve] --replicas composes whole-engine device slices; "
              "--tp/--disaggregate inside a replica is not wired yet",
              file=sys.stderr)
        return 2
    cfg = getattr(T, args.model)
    key = jax.random.PRNGKey(args.seed)
    params = T.init_params(key, cfg)
    if args.param_scale != 1.0:
        params = jax.tree.map(
            lambda x: (x * args.param_scale).astype(x.dtype), params)

    swap_dir = None
    if args.swap_at is not None:
        swap_dir = args.swap_ckpt
        if swap_dir is None:
            # no checkpoint given: save a seed+1 init to swap to — the
            # "new weights" stand-in a train loop would have produced
            from distributed_training_sandbox_tpu.resilience.state \
                import Checkpointer, RunState
            swap_dir = tempfile.mkdtemp(prefix="serve_swap_")
            new_params = T.init_params(
                jax.random.PRNGKey(args.seed + 1), cfg)
            if args.param_scale != 1.0:
                new_params = jax.tree.map(
                    lambda x: (x * args.param_scale).astype(x.dtype),
                    new_params)
            ck = Checkpointer(swap_dir)
            ck.save(RunState(params=new_params, step=0), wait=True)
            ck.close()

    rng = np.random.default_rng(args.seed)
    trace = build_trace(rng, args.requests, args.rate, cfg.vocab_size,
                        args.max_seq_len, tenants=args.tenants,
                        overlap_frac=args.overlap_frac,
                        sys_len=args.sys_len)
    deadline_s = (None if args.deadline_ms is None
                  else args.deadline_ms / 1e3)
    backoff_s = args.burst_ms / 1e3

    run_cfg = {"num_steps": 0, "batch_size": args.max_batch,
               "sequence_length": args.max_seq_len, "seed": args.seed,
               "requests": args.requests, "rate": args.rate,
               "page_size": args.page_size,
               "replicas": args.replicas,
               "inject_fault": args.inject_fault,
               "deadline_ms": args.deadline_ms,
               "swap_at": args.swap_at,
               "max_queue": args.max_queue,
               # everything sim_bench --validate needs to rebuild THIS
               # run's trace and knobs bit-for-bit
               "tenants": args.tenants,
               "overlap_frac": args.overlap_frac,
               "sys_len": args.sys_len,
               "prefill_chunk": args.prefill_chunk,
               "sync_every": args.sync_every,
               "burst_ms": args.burst_ms,
               "prefix_cache": args.prefix_cache,
               "spec_k": args.spec_k,
               "flash_prefill": args.flash_prefill}
    prof = None
    if args.profile:
        from distributed_training_sandbox_tpu.utils.profiling import (
            ProfileSchedule, Profiler)
        prof = Profiler(trace_dir=args.trace_dir,
                        schedule=ProfileSchedule(skip_first=2, wait=1,
                                                 warmup=2, active=8))
    failures = []
    with TelemetryRun("fleet", model=args.model, config=run_cfg,
                      profiler=prof,
                      metrics_port=args.metrics_port) as telem:
        if telem.metrics_server is not None:
            print(f"[serve] metrics: {telem.metrics_server.url}",
                  flush=True)
        fleet = Fleet(
            params, cfg, replicas=args.replicas,
            watchdog_timeout_s=args.watchdog_timeout,
            fault=args.inject_fault, telem=telem,
            max_queue=args.max_queue, burst_s_prior=backoff_s,
            max_batch=args.max_batch, page_size=args.page_size,
            max_seq_len=args.max_seq_len,
            prefill_chunk=args.prefill_chunk,
            sync_every=args.sync_every, kv_quant=args.kv_quant,
            hbm_budget_gb=args.hbm_budget_gb,
            prefix_cache=args.prefix_cache, spec_k=args.spec_k,
            draft_layers=args.draft_layers if args.spec_k else None,
            flash_prefill=args.flash_prefill)
        admitted = []
        offset = 0.0
        for t, prompt, new in trace:
            # queue_full backpressure INTO the driver: the open loop
            # slows down by one modeled burst per overflow, the way a
            # load balancer's 429s pace real clients
            r = fleet.submit(prompt, max_new_tokens=new,
                             arrival_s=t + offset,
                             deadline_s=deadline_s)
            if isinstance(r, Rejection):
                if r.reason == "queue_full":
                    offset += backoff_s
            else:
                admitted.append(r)
        if args.swap_at is not None:
            fleet.schedule_swap(swap_dir, after_completed=args.swap_at)
        fleet.run()
        slo = fleet.slo_report()
        print(f"[serve] fleet x{args.replicas}: {slo['completed']} "
              f"completed / {slo['shed']} shed / {slo['dropped']} "
              f"dropped of {args.requests}; live "
              f"{slo['live']}/{slo['replicas']}, TTFT p50 "
              f"{slo['ttft_ms']['p50']} ms p99 {slo['ttft_ms']['p99']} "
              f"ms; events: "
              f"{[e['event'] for e in slo['events']] or 'none'}",
              flush=True)

        if slo["dropped"] > 0:
            failures.append(
                f"{slo['dropped']} admitted request(s) dropped "
                f"(rids {fleet.dropped()[:8]}) — the zero-drop "
                f"invariant is broken")
        if slo["completed"] + slo["shed"] != args.requests:
            failures.append(
                f"bookkeeping leak: {slo['completed']} completed + "
                f"{slo['shed']} shed != {args.requests} offered")
        retr = slo["recompiles_after_warmup"]
        if retr is None or retr > 0:
            failures.append(f"jit cache grew after warmup: {retr}")
        if args.swap_at is None:
            for req in admitted[:args.check_parity]:
                ref = np.asarray(generate(
                    params, req.prompt[None], cfg,
                    max_new_tokens=req.max_new_tokens,
                    kv_quant=args.kv_quant,
                    cache_capacity=fleet.view_capacity))[0]
                got = np.asarray(req.tokens, np.int32)
                if got.shape != ref.shape or not (got == ref).all():
                    failures.append(
                        f"rid {req.rid}: tokens diverge from one-shot "
                        f"generate (got {got.tolist()[:8]}..., ref "
                        f"{ref.tolist()[:8]}...)")
            slo["parity_checked"] = min(args.check_parity,
                                        len(admitted))
        slo["failures"] = failures
        telem.finalize(fleet=slo)

    if args.export_timeline and telem.run_dir:
        sys.path.insert(0, str(Path(__file__).resolve().parent))
        from export_timeline import main as export_main
        export_main([telem.run_dir])

    print(json.dumps({k: v for k, v in slo.items()
                      if k not in ("rejections", "events")}, indent=1))
    for f in failures:
        print(f"[serve] FAIL: {f}", file=sys.stderr, flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
