"""Reproduce the reference's FSDP memory-waterline observations on TPU.

The reference documents what its profiler shows for FSDP SmolLM3-3B on
2×A100 (``/root/reference/README.md:22-33``): ~12 GB static at rest
(bf16 param shard + AdamW state + metadata), a sawtooth of per-layer
gathers through forward/backward, and **three ~4 GB fp32 spikes** at the
loss — logits, log-probs, and grad-wrt-log-probs, each (B·S=8192) × 128k
vocab × 4 bytes.  This script regenerates the same phase accounting for
the TPU build and writes ``EXPERIMENTS.md``.

Methodology: the waterline is assembled from the two sources that are
exact on every backend (a v5e's allocator stats are printed beside them
when present; its ``peak_bytes_in_use`` leaves out a program's
temporaries):

  * component sizes by tensor walk (``utils/memory.py``) — the at-rest
    waterline (params / grads / optimizer state), same accounting as the
    reference's ``print_memory_stats``;
  * XLA's compile-time allocator plan (``compiled.memory_analysis()``) —
    argument + output + temp buffer sizes for each jitted step variant.
    ``temp_size_in_bytes`` is the compiler's actual activation/scratch
    high-water reservation, i.e. exactly the quantity the reference
    eyeballs off its profiler's memory timeline.

The A/B that matters: the dense-loss step (the reference's design)
versus the streamed-vocab-loss step (this repo's) — the three spikes
exist in the former's temp plan and are absent from the latter's.

    python scripts/memory_waterline.py [--out EXPERIMENTS.md]
"""

from __future__ import annotations

import argparse
import dataclasses
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

GB = 1 << 30


def analyze(step, *args) -> dict:
    """Compile-time memory plan; where the compiler validates HBM fit,
    an over-budget plan comes back as the compiler's own
    used-vs-capacity numbers instead (parsed by the shared
    ``utils.memory.parse_hbm_oom`` — the same helper the memory
    planner's compiler-OOM fallback uses)."""
    from distributed_training_sandbox_tpu.utils.memory import parse_hbm_oom
    try:
        c = step.lower(*args).compile()
    except Exception as e:
        oom = parse_hbm_oom(str(e))
        if oom:
            return {"oom": True, "needed_gb": oom[0],
                    "capacity_gb": oom[1]}
        raise
    ma = c.memory_analysis()
    return {
        "args_gb": ma.argument_size_in_bytes / GB,
        "out_gb": ma.output_size_in_bytes / GB,
        "temp_gb": ma.temp_size_in_bytes / GB,
        "alias_gb": ma.alias_size_in_bytes / GB,
    }


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--out", default="EXPERIMENTS.md")
    p.add_argument("--model", default="SMOLLM3_3B_L8")
    p.add_argument("--seq", type=int, default=8192)
    p.add_argument("--batch", type=int, default=2)
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from distributed_training_sandbox_tpu.models import transformer as T
    from distributed_training_sandbox_tpu.parallel import fsdp
    from distributed_training_sandbox_tpu.utils import make_mesh
    from distributed_training_sandbox_tpu.utils.memory import (
        device_memory_stats, tree_size_mb)

    cfg = getattr(T, args.model)
    mesh = make_mesh()
    ws = int(mesh.devices.size)
    B, S = max(args.batch, ws), args.seq
    platform = jax.devices()[0].platform

    params = T.init_params(jax.random.PRNGKey(0), cfg)
    shards = fsdp.shard_params_fsdp(params, mesh)
    del params
    opt = fsdp.init_fsdp_opt_state(shards)
    ids = jnp.zeros((B, S), jnp.int32)
    batch = (ids, ids)

    p_mb = tree_size_mb(shards)
    o_mb = tree_size_mb(opt)

    ids1 = jnp.zeros((ws, S), jnp.int32)
    variants = {}
    for name, over, b in (
        ("streamed_loss", {}, batch),
        ("streamed_save_dots", {"remat_policy": "save_dots"}, batch),
        ("dense_loss", {"loss_vocab_chunk": None}, batch),
        ("streamed_no_remat", {"remat": False}, batch),
        ("streamed_loss_b1", {}, (ids1, ids1)),
        ("dense_loss_b1", {"loss_vocab_chunk": None}, (ids1, ids1)),
    ):
        vcfg = dataclasses.replace(cfg, **over)
        step = fsdp.make_fsdp_train_step(shards, vcfg, mesh, donate=False)
        variants[name] = analyze(step, shards, opt, b)
        variants[name]["batch"] = int(b[0].shape[0])
        print(f"[waterline] {name}: {variants[name]}", flush=True)

    spike = B * S * cfg.vocab_size * 4 / GB
    runtime = device_memory_stats()
    runtime_note = (
        f"live allocator stats: {runtime}"
        if runtime and any(runtime.values()) else
        "this backend reports no runtime allocator stats — "
        "compile-time plan used instead")

    def vrow(name):
        v = variants[name]
        if v.get("oom"):
            return (f"| {name} | {v['batch']} | — | **does not fit: "
                    f"{v['needed_gb']:.2f} GB needed / "
                    f"{v['capacity_gb']:.2f} GB HBM** | — |")
        return (f"| {name} | {v['batch']} | {v['args_gb']:.2f} "
                f"| {v['temp_gb']:.2f} | {v['out_gb']:.2f} |")

    def spike_story():
        dense, stream = variants["dense_loss"], variants["streamed_loss"]
        if dense.get("oom"):
            head = (f"* `dense_loss` at batch {B} does not even compile: "
                    f"XLA's allocator wants **{dense['needed_gb']:.2f} GB** "
                    f"against {dense['capacity_gb']:.2f} GB of HBM — the "
                    f"spike buffers are right there in the failed plan.")
        else:
            head = (f"* `dense_loss` plans {dense['temp_gb']:.2f} GB of "
                    f"temp — the spikes are in the compiler's plan.")
        d1, s1 = variants["dense_loss_b1"], variants["streamed_loss_b1"]
        if not d1.get("oom") and not s1.get("oom"):
            per = B // max(d1["batch"], 1)
            tail = (f"* At batch {d1['batch']} (one {spike / per:.2f} GB "
                    f"logits-shaped buffer), the plans compile side by "
                    f"side: dense {d1['temp_gb']:.2f} GB temp vs streamed "
                    f"{s1['temp_gb']:.2f} GB — "
                    f"{d1['temp_gb'] - s1['temp_gb']:.2f} GB of loss-phase "
                    f"buffers removed by streaming.")
        else:
            tail = ("* The batch-1 dense plan also exceeds HBM; the spike "
                    "magnitude is the analytic B·S·V·4 above.")
        return head + "\n" + tail

    doc = f"""# EXPERIMENTS — FSDP memory waterline on TPU

Twin of the reference's measured memory phases
(`/root/reference/README.md:22-33`).  Regenerate with
`python scripts/memory_waterline.py` (run on the target hardware).

Config: `{args.model}` (the 3B architecture at {cfg.num_hidden_layers}
layers), batch {B} × seq {S}, vocab {cfg.vocab_size:,}, {ws}-device
`{platform}` mesh, explicit-FSDP step (AdamW, bf16 params).

## At rest (the reference's "~12 GB static" line)

The reference holds a bf16 3B 2-way shard + bf16 AdamW state ≈ 3.1 + 6.2
GB/device.  This build, per device (tensor walk, `utils/memory.py`):

| component | GB/device |
|---|---|
| param shards | {p_mb / 1024:.2f} |
| AdamW state (mu+nu) | {o_mb / 1024:.2f} |
| gradients (transient, = params) | {p_mb / 1024:.2f} |
| **total at rest** | **{(p_mb + o_mb) / 1024:.2f}** |

## Step memory plan (XLA `memory_analysis`, {platform})

`temp` is XLA's allocated scratch/activation high-water for one whole
train step — the quantity whose sawtooth+spikes the reference reads off
its profiler timeline.  ({runtime_note}.)

| step variant | batch | args GB | temp GB | out GB |
|---|---|---|---|---|
""" + "\n".join(vrow(n) for n in variants) + f"""

## The three ~4 GB spikes, found and removed

One fp32 logits-shaped buffer at this config is B·S·V·4 =
**{spike:.2f} GB** at batch {B} ({spike / B:.2f} GB at batch 1 — the
same B·S=8192 shape as the reference's trio of ~4 GB spikes: logits,
log-probs, grad-wrt-log-probs).

{spike_story()}
* `streamed_loss` (this repo's `loss_vocab_chunk`
  = {cfg.loss_vocab_chunk}) plans
  {variants['streamed_loss']['temp_gb']:.2f} GB of temp at batch {B}:
  the tokens stream through the head in row blocks over the whole
  vocabulary (a block's fp32 logits are the budget B·S·{cfg.loss_vocab_chunk}·4
  bytes), each block making its gradient from its own logits into a
  fp32 (V, H) dW carry, so no (B, S, V) tensor ever exists — forward
  OR backward.  This is what lets one 16 GB v5e train the 8-layer 3B
  geometry at seq 8192 at all.
* `streamed_no_remat` isolates rematerialisation: without
  `jax.checkpoint` on the layer scan the activation plan is
  {'**unplannable (exceeds HBM: ' + format(variants['streamed_no_remat'].get('needed_gb', 0), '.2f') + ' GB needed)**'
   if variants['streamed_no_remat'].get('oom') else
   format(variants['streamed_no_remat']['temp_gb'], '.2f') + ' GB of temp'}
  (all {cfg.num_hidden_layers} layers' activations held for the
  backward) vs {variants['streamed_loss']['temp_gb']:.2f} GB with remat
  — the FLOPs-for-HBM trade the reference's `reshard_after_forward`
  comments gesture at, applied to activations.
* `streamed_save_dots` (remat_policy="save_dots") keeps every matmul
  output resident so the backward recomputes only elementwise ops:
  {'the plan exceeds HBM at this config (' + format(variants['streamed_save_dots'].get('needed_gb', 0), '.2f') + ' GB needed)'
   if variants['streamed_save_dots'].get('oom') else
   'it plans ' + format(variants['streamed_save_dots']['temp_gb'], '.2f') + ' GB of temp'}
  — the FLOPs-vs-HBM middle point between full remat and no remat
  (this script measures no throughput).

## Reading guide vs the reference

| reference observation (README.md:22-33) | this build |
|---|---|
| ~12 GB at rest (3B 2-way bf16 + AdamW) | {(p_mb + o_mb) / 1024:.2f} GB at rest ({cfg.num_hidden_layers}-layer geometry, 1 device) |
| per-layer gather sawtooth in fwd/bwd | same choreography (`fsdp_layer_gather` scopes in traces); amplitude = one layer's full params |
| 3 × ~4 GB fp32 loss spikes | absent by design (streamed vocab); dense variant reproduces them in-plan |
"""
    Path(args.out).write_text(doc)
    print(f"[waterline] wrote {args.out}")


if __name__ == "__main__":
    main()
