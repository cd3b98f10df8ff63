"""Fully-sharded pretraining of the real transformer LM — runnable twin of
reference ``fsdp/train_fsdp.py``.

Same flow: model from config (random init, bf16), TinyStories packed dataset
(synthetic fallback offline), per-layer shard/gather (ZeRO-3) or persisted
gather (ZeRO-2) via ``--no-reshard-after-forward``, AdamW-on-shards,
warmup-aware PerformanceTracker (tokens/s + TFLOPS/device), rank-0 profiler
(wait=5 warmup=5 active=10 — reference ``fsdp/train_fsdp.py:124-137``).
Runs under the resilience supervisor: ``--checkpoint-dir/--checkpoint-every/
--resume/--max-restarts`` give preemption-safe bit-exact resume of the
dp-sharded params + opt state, data cursor included.

Memory-planned: every run prints its predicted HBM waterline
(``memory_plan/``); ``--hbm-budget-gb`` rejects predicted-over-budget
configs before any compile, ``--auto-fit`` lets the planner pick
remat × accum × quant × offload to fit the target batch, and
``--offload opt|opt_act`` parks the Adam moments (and named remat saves)
in pinned host memory under a declared transfer contract.

Usage:
  python scripts/train_fsdp.py --num-steps 20 --sequence-length 8192 \
      [--model smollm3-3b|smollm3-350m|tiny] [--variant explicit|auto] \
      [--no-reshard-after-forward] [--cpu-devices 8] [--batch-size N]

The collective ledger on a TPU: profiling is on by default (steps 5.. are
traced), so any run of eight steps or more leaves ``collectives.json`` in
its run dir (``./runs/<run_id>/``: every collective site of the compiled
step with its payload, in-flight time and bus GB/s, joined against the
FSDP contract) and the comm/compute split in ``summary.json``, both read
from the profiler's ``.xplane.pb``; ``python scripts/report.py runs``
renders the NCCL-vs-ICI table from them.  On the four chips of a v5e 2x2:

  HF_HUB_OFFLINE=1 python scripts/train_fsdp.py --model smollm3-3b \
      --attention flash --sequence-length 4096 --num-steps 8

(``HF_HUB_OFFLINE=1`` takes the synthetic token stream at once on a
machine with no network; at 8,192 tokens a chip the memory planner
refuses the preset, whose loss head is not streamed.)
"""

from __future__ import annotations

import argparse
import dataclasses
import sys
from pathlib import Path

# Prepend the checkout root so the source tree always wins over any
# installed copy of the package (`pip install -e .` makes this a no-op).
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from distributed_training_sandbox_tpu.models import MODEL_REGISTRY as MODELS  # noqa: E402


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--cpu-devices", type=int, default=0)
    p.add_argument("--model", choices=sorted(MODELS), default="tiny")
    p.add_argument("--variant", choices=["explicit", "auto"],
                   default="explicit")
    p.add_argument("--no-reshard-after-forward", dest="reshard",
                   action="store_false", default=True)
    p.add_argument("--attention", choices=["xla", "flash"], default=None)
    p.add_argument("--remat-policy",
                   choices=["full", "save_attn", "save_dots"],
                   default=None)
    args, rest = p.parse_known_args(argv)

    if args.cpu_devices:
        from distributed_training_sandbox_tpu.utils import use_cpu_devices
        use_cpu_devices(args.cpu_devices)

    from distributed_training_sandbox_tpu.utils import TrainConfig
    from distributed_training_sandbox_tpu import resilience as RZ

    cfg = TrainConfig.from_args(
        rest, sequence_length=256 if args.model == "tiny" else 8192)
    sup = RZ.Supervisor.from_config(
        cfg, strategy="fsdp",
        extra_fingerprint={"model": args.model, "variant": args.variant})
    return sup.run(lambda ctx: _leg(args, rest, cfg, ctx))


def _leg(args, rest, cfg, ctx):
    import itertools

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from distributed_training_sandbox_tpu.utils import (
        set_seed, make_mesh, get, Profiler, ProfileSchedule,
        PerformanceTracker, print_memory_stats)
    from distributed_training_sandbox_tpu.utils.flops import (
        get_model_flops_per_token)
    from distributed_training_sandbox_tpu.telemetry import TelemetryRun
    from distributed_training_sandbox_tpu.runtime import (
        DevicePrefetcher, StepPump)
    from distributed_training_sandbox_tpu import resilience as RZ
    from distributed_training_sandbox_tpu.models import transformer as T
    from distributed_training_sandbox_tpu.parallel import fsdp
    from distributed_training_sandbox_tpu.ops import count_collectives
    from distributed_training_sandbox_tpu.data import (
        make_packed_dataset, packed_batches)

    def flag_given(flag):
        return any(r == flag or r.startswith(flag + "=") for r in rest or [])

    mcfg: T.TransformerConfig = getattr(T, MODELS[args.model])
    if args.attention:
        mcfg = dataclasses.replace(mcfg, attention_impl=args.attention)
    if args.remat_policy:
        mcfg = dataclasses.replace(mcfg, remat_policy=args.remat_policy)
    # Consume the shared --precision knob (the reference's fsdp dir declares
    # `--precision fp8` and ignores it — its quirk #9; this one is real).
    if cfg.precision.startswith("int8"):
        mcfg = dataclasses.replace(mcfg, matmul_precision=cfg.precision)
    elif cfg.precision == "fp32":
        mcfg = dataclasses.replace(mcfg, dtype=jnp.float32)
    mesh = make_mesh()
    ws = get("ws")
    # global batch = 1 per device by default (reference's bs=1 dataloader,
    # train_fsdp.py:72); must stay divisible by the dp axis.
    if not flag_given("--batch-size"):
        cfg.batch_size = ws
    if cfg.batch_size % ws:
        raise SystemExit(f"--batch-size {cfg.batch_size} must be divisible "
                         f"by device count {ws}")
    print(f"[fsdp] model={args.model} ({mcfg.param_count()/1e9:.3f}B) "
          f"variant={args.variant} reshard_after_forward={args.reshard} "
          f"mesh={dict(mesh.shape)} platform={jax.devices()[0].platform}")

    # ---- memory planner: pre-flight waterline + auto-fit ---------------
    from distributed_training_sandbox_tpu import memory_plan as MP
    from distributed_training_sandbox_tpu.utils.memory import (
        hbm_capacity_gb)
    budget = cfg.hbm_budget_gb or hbm_capacity_gb()
    state_precision = "full"
    if cfg.auto_fit:
        if args.variant != "explicit":
            raise SystemExit("--auto-fit tunes the explicit step's knobs "
                             "(remat/accum/quant/offload); drop "
                             "--variant auto")
        mplan = MP.plan(mcfg, batch=cfg.batch_size, seq=cfg.sequence_length,
                        ws=ws, hbm_budget_gb=budget)
        chosen = mplan.best.candidate
        print(f"[fsdp] memory plan: {mplan.summary()}")
        mcfg = chosen.apply_to(mcfg)
        cfg.accum_steps = chosen.accum_steps
        cfg.offload = chosen.offload
        state_precision = chosen.state_precision
    pred = MP.analytic_waterline(
        mcfg, batch=cfg.batch_size, seq=cfg.sequence_length, ws=ws,
        accum_steps=max(cfg.accum_steps, 1), state_precision=state_precision,
        offload=cfg.offload, capacity_gb=budget)
    print(f"[fsdp] predicted waterline: {pred.gb:.2f} GB/device "
          f"(budget {budget:.2f} GB)" if budget is not None else
          f"[fsdp] predicted waterline: {pred.gb:.2f} GB/device")
    if pred.fits is False and not cfg.auto_fit:
        raise SystemExit(
            f"predicted waterline {pred.gb:.2f} GB exceeds the "
            f"{budget:.2f} GB budget — rejected pre-compile; rerun with "
            f"--auto-fit to search remat/accum/quant/offload, or raise "
            f"--hbm-budget-gb")
    if cfg.offload == "opt_act":
        if mcfg.remat_policy not in ("save_attn", "save_dots_q8"):
            raise SystemExit(
                "--offload opt_act redirects NAMED remat saves to host; "
                "pass --remat-policy save_attn (or save_dots_q8)")
        mcfg = dataclasses.replace(mcfg, offload_activations=True)

    key = set_seed(cfg.seed)
    params = T.init_params(key, mcfg)
    shards = fsdp.shard_params_fsdp(params, mesh)
    del params
    if state_precision == "int8":
        opt_state = fsdp.init_fsdp_opt_state8(shards)
    else:
        opt_state = fsdp.init_fsdp_opt_state(shards)
    oplan = MP.plan_offload(cfg.offload, opt_state)
    if oplan.supported and cfg.offload != "none":
        # park the Adam moments in pinned host memory at rest; the step
        # streams them around the update under the declared contract
        opt_state = MP.offload_tree(opt_state)
        print(f"[fsdp] offload={cfg.offload}: {oplan.n_state_leaves} "
              f"state leaves ({oplan.state_bytes / 2**30:.2f} GB) "
              f"host-resident")
    print_memory_stats("fsdp-at-rest", params=shards, opt_state=opt_state)
    # resume BEFORE lowering: the contract below then checks the restored
    # state's actual sharding choreography
    rs = ctx.restore(like=RZ.RunState(params=shards, opt_state=opt_state,
                                      prng_key=key))
    if rs is not None:
        shards, opt_state = rs.params, rs.opt_state

    if cfg.overlap != "none" and args.variant != "explicit":
        raise SystemExit(f"--overlap {cfg.overlap} rewires the explicit "
                         f"shard_map choreography; the auto variant's "
                         f"schedule belongs to XLA (drop --variant auto)")
    if cfg.offload != "none" and args.variant != "explicit":
        raise SystemExit(f"--offload {cfg.offload} streams the optimizer "
                         f"state around the explicit step; the auto "
                         f"variant's placement belongs to XLA (drop "
                         f"--variant auto)")
    if cfg.accum_steps > 1 and (cfg.batch_size // ws) % cfg.accum_steps:
        raise SystemExit(f"--accum-steps {cfg.accum_steps} must divide "
                         f"the per-device batch "
                         f"{cfg.batch_size}/{ws}={cfg.batch_size // ws}")
    if args.variant == "explicit":
        step = fsdp.make_fsdp_train_step(
            shards, mcfg, mesh, reshard_after_forward=args.reshard,
            overlap=cfg.overlap, accum_steps=cfg.accum_steps,
            offload=cfg.offload, state_precision=state_precision)
    else:
        step = fsdp.make_fsdp_auto_train_step(shards, mcfg, mesh)

    input_ids, labels = make_packed_dataset(
        cfg.sequence_length, mcfg.vocab_size,
        num_tokens=max(cfg.batch_size * cfg.num_steps, 8)
        * (cfg.sequence_length + 1))
    print(f"[fsdp] dataset: {len(input_ids)} windows of "
          f"{cfg.sequence_length} tokens")

    flops_tok = get_model_flops_per_token(mcfg, cfg.sequence_length)
    tracker = PerformanceTracker(
        warmup_steps=min(5, max(cfg.num_steps - 1, 0)),
        flops_per_token=flops_tok, num_devices=ws)
    prof = Profiler(trace_dir=cfg.trace_dir,
                    schedule=ProfileSchedule(skip_first=0, wait=5, warmup=5,
                                             active=10)) if cfg.profile else None

    probe = (jnp.zeros((cfg.batch_size, cfg.sequence_length), jnp.int32),) * 2
    counts = count_collectives(step, shards, opt_state, probe)
    print(f"[fsdp] per-step collectives (HLO): {counts}")
    # the auto variant's choreography is XLA's choice, not ours to
    # contract; ring_fused's decomposed-matmul site counts are pinned by
    # tests/test_overlap.py rather than a registry formula
    verdict = None
    cname = ("fsdp_ring" if cfg.overlap == "ring"
             else "fsdp_offload" if cfg.offload != "none" else "fsdp")
    if args.variant == "explicit" and cfg.overlap != "ring_fused":
        from distributed_training_sandbox_tpu.analysis import (
            evaluate_contract)
        # the remat'd scan body re-runs its layer_hook in the backward:
        # with per-layer resharding every stacked leaf is gathered twice
        regather = len(jax.tree.leaves(shards["layers"])) \
            if mcfg.remat and args.reshard else 0
        verdict = evaluate_contract(cname, counts, params=shards,
                                    mesh=mesh,
                                    n_layers=mcfg.num_hidden_layers,
                                    offload=oplan.to_dict(),
                                    regather_leaves=regather)
        print(f"[fsdp] contract[{cname}]: {verdict.summary()}")
    ctx.verify_contract(verdict)

    # partition-rule verdict for the manifest: committed param shardings
    # vs the rule-derived specs (the compiled-HLO drift lint is
    # scripts/lint_sharding.py --rules' job)
    from distributed_training_sandbox_tpu.analysis import (
        rules_manifest_verdict)
    rules_verdict = rules_manifest_verdict(cname, params=shards)
    print(f"[fsdp] rules[{cname}]: "
          f"{'ok' if rules_verdict['ok'] else 'MISMATCH'} "
          f"({rules_verdict.get('checked', 0)} leaves checked)")

    # predicted vs compiler-reported waterline for the manifest: the
    # compile-side number costs an AOT compile, so it is only taken when
    # the run is explicitly memory-planned (a budget or auto-fit given)
    mem_record = {**pred.to_dict(), "budget_gb": budget,
                  "offload": oplan.to_dict()}
    if cfg.auto_fit:
        mem_record["auto_fit"] = mplan.best.candidate.label()
    if (cfg.auto_fit or cfg.hbm_budget_gb) and args.variant == "explicit":
        try:
            compiled = MP.predict_from_step(step, shards, opt_state,
                                            probe, capacity_gb=budget)
            mem_record["compiled_gb"] = round(compiled.gb, 3)
            mem_record["compiled_source"] = compiled.source
            print(f"[fsdp] compiler-reported waterline: "
                  f"{compiled.gb:.2f} GB/device (predicted "
                  f"{pred.gb:.2f})")
        except Exception as e:  # noqa: BLE001 - prediction must not kill runs
            mem_record["compiled_error"] = str(e)[:200]

    tokens_per_step = cfg.batch_size * cfg.sequence_length
    batches = packed_batches(input_ids, labels, cfg.batch_size,
                             epochs=cfg.num_epochs * cfg.num_steps)
    if ctx.data_cursor:
        # resume: the dataset rebuild above is seed-deterministic — skip
        # the batches segment 1 already consumed
        batches = itertools.islice(batches, ctx.data_cursor, None)
    # prefetcher stages (ids, labels) committed under the step's dp batch
    # sharding; pump retires losses per the sync policy
    pref = DevicePrefetcher(batches, mesh=mesh, spec=P("dp"),
                            depth=cfg.prefetch_depth)
    with pref, TelemetryRun(
            "fsdp", config=cfg, mesh=mesh, model=args.model,
            collective_counts=counts, profiler=prof,
            contract=verdict.to_dict() if verdict else None,
            rules=rules_verdict,
            lineage=ctx.manifest_lineage(),
            extra={"variant": args.variant,
                   "reshard_after_forward": args.reshard,
                   "memory_plan": mem_record}) as telem:
        pref.spans = telem.spans   # prefetch waits onto the timeline
        pref.metrics = telem.metrics
        with StepPump(telem=telem, tracker=tracker, mode=cfg.dispatch,
                      sync_every=cfg.sync_every,
                      max_in_flight=cfg.max_in_flight) as pump:
            for i, batch in zip(range(ctx.start_step, cfg.num_steps), pref):
                if ctx.should_stop(i):
                    break
                if i == ctx.start_step:
                    # ledger join: compiled text at the loop's exact
                    # shardings (the staged batch, not a host copy); the
                    # planner record rides along so the memory ledger can
                    # verdict measured-vs-predicted
                    telem.attach_step_hlo(step, shards, opt_state, batch,
                                          prediction=mem_record)
                shards, opt_state, loss = step(shards, opt_state, batch)
                log = (lambda lf, i=i:
                       print(f"[fsdp] step {i:3d} loss {lf:.4f}")) \
                    if i % 5 == 0 or i == cfg.num_steps - 1 else None
                synced = pump.emit(loss, tokens=tokens_per_step, log=log)
                ctx.after_step(i, synced, lambda i=i: RZ.RunState(
                    params=shards, opt_state=opt_state, step=i,
                    data_cursor=i + 1, prng_key=key,
                    loss_log=ctx.full_losses(pump.losses)))
        ctx.finalize(telem)
    metrics = pump.metrics or {}
    print(f"[fsdp] host syncs: {pump.host_sync_count} "
          f"({pump.sync_breakdown})")
    if prof:
        from distributed_training_sandbox_tpu.utils.trace_analysis import (
            split_from_trace)
        sp = split_from_trace(cfg.trace_dir)
        if sp:
            print(sp.report("fsdp"))

    print_memory_stats("fsdp-final", params=shards, opt_state=opt_state)
    if metrics:
        print(f"[fsdp] tokens/s {metrics['tokens_per_second']:.1f} "
              f"steps/s {metrics['steps_per_second']:.3f} "
              f"TFLOPS/dev {metrics.get('tflops_per_device', 0):.2f} "
              f"avg_loss {metrics.get('avg_loss', float('nan')):.4f}")
    if telem.run_dir:
        print(f"[fsdp] telemetry in {telem.run_dir}")
    metrics["losses"] = ctx.full_losses(pump.losses)
    return metrics


if __name__ == "__main__":
    main()
