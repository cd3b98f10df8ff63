"""MoE quality A/B: is the MoE throughput win real at matched wall-clock?

r3 headlined MoE tok/s at capacity factor 1.0 — an operating point that
drops ~9.7% of (token, assignment) pairs at init — with no quality
evidence.  The reference's whole fp8 dir exists to make a *fair*
throughput comparison (``fp8/fp8_benchmark.py:162-188``); this is the
MoE equivalent:

  * three legs — dense 3B-L8, MoE cf 2.0, MoE cf 1.0 (8 experts ×
    ffn 2752 = dense MLP FLOPs split 4-ways active; grouped dispatch,
    the timed headline path) — each trained for the SAME wall-clock
    budget on the SAME seeded batch stream with the same warmup+cosine
    schedule;
  * every leg logs every step's train loss + wall time, and a fixed
    held-out eval loss every ``--eval-every`` steps;
  * MoE legs log the drop-rate trajectory as the router trains,
    measured with the dispatch's OWN capacity rule
    (``expert.grouped_drop_fraction`` on the live router's assignments —
    the aux load-balance loss is what moves it);
  * output: ``moe_results/quality_ab_<platform>.json`` + plots
    (loss vs wall-clock, loss vs step, drop rate vs step).

The verdict the json carries: eval loss at matched wall-clock, dense vs
each capacity factor — the number the MoE throughput headline must be
restated against.

    python scripts/moe_quality_ab.py --seconds 420
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

def _base_moe() -> dict:
    # the ONE named MoE flagship geometry
    from distributed_training_sandbox_tpu.models.transformer import (
        SMOLLM3_3B_L8_MOE as M)
    return {"n_experts": M.n_experts, "moe_ffn": M.moe_ffn,
            "moe_dispatch": M.moe_dispatch}


@contextlib.contextmanager
def _mlp_drop_tap(T, expert_mod):
    """Swap ``transformer._mlp_block``'s aux output for the grouped
    dispatch's drop fraction while a metric function is being traced —
    the routing and the capacity rule are the real ones
    (``_route_topk`` + ``grouped_drop_fraction``), so this cannot drift
    from what the timed train step enforces."""
    orig = T._mlp_block

    def with_drop(r, layer, *, cfg):
        mlp, _lb = orig(r, layer, cfg=cfg)
        B, S, H = r.shape
        _, experts, _ = expert_mod._route_topk(
            r.reshape(B * S, H), layer["w_router"], cfg.moe_top_k)
        drop = expert_mod.grouped_drop_fraction(
            experts, cfg.n_experts, cfg.moe_group_size,
            cfg.moe_capacity_factor)
        return mlp, drop

    T._mlp_block = with_drop
    try:
        yield
    finally:
        T._mlp_block = orig


def run_leg(name: str, cfg_overrides: dict, seconds: float, seq: int,
            bs: int, peak_lr: float, warmup: int, eval_every: int,
            data, eval_batch, base: str = "SMOLLM3_3B_L8") -> dict:
    import jax
    import jax.numpy as jnp
    from distributed_training_sandbox_tpu.models import transformer as T
    from distributed_training_sandbox_tpu.parallel import expert as E
    from distributed_training_sandbox_tpu.parallel import fsdp, optim
    from distributed_training_sandbox_tpu.utils import make_mesh, set_seed

    over = dict(cfg_overrides)
    over.setdefault(
        "attention_impl",
        "flash" if jax.default_backend() == "tpu" else "xla")
    mcfg = dataclasses.replace(getattr(T, base), **over)
    mesh = make_mesh()
    key = set_seed(42)
    params = T.init_params(key, mcfg)
    shards = fsdp.shard_params_fsdp(params, mesh)
    del params
    opt = fsdp.init_fsdp_opt_state(shards)
    # long horizon: decay is effectively flat across legs; warmup matters
    sched = optim.warmup_cosine_schedule(peak_lr, warmup, 100_000)
    step = fsdp.make_fsdp_train_step(shards, mcfg, mesh, lr_schedule=sched)

    eval_loss = jax.jit(lambda p, b: T.lm_loss(p, b, mcfg))
    drop_fn = None
    if mcfg.n_experts:
        with _mlp_drop_tap(T, E):
            drop_fn = jax.jit(
                lambda p, ids: T.hidden_states(
                    p, ids, mcfg, return_aux=True)[1]
                / mcfg.num_hidden_layers)
            ids_aval = jax.ShapeDtypeStruct((bs, seq), jnp.int32)
            drop_fn = drop_fn.lower(shards, ids_aval).compile()

    ii, ll = data
    n = len(ii)
    losses, times, evals, drops = [], [], [], []
    i = 0
    # The budget clock counts TRAIN time only: eval and drop-metric
    # computations run OFF the clock.  The r4 A/B timed them inside the
    # budget, so MoE legs (which also pay for drop_fn) weren't
    # throughput-comparable with dense — the 9k-vs-16k tok/s
    # inconsistency the verdict flagged (Weak #3).
    train_s = 0.0
    while True:
        j = i % (n // bs)
        batch = (jnp.asarray(ii[j * bs:(j + 1) * bs]),
                 jnp.asarray(ll[j * bs:(j + 1) * bs]))
        if drop_fn is not None and i % eval_every == 0:
            drops.append((i, float(drop_fn(shards, batch[0]))))
        if i % eval_every == 0:
            evals.append((i, float(eval_loss(shards, eval_batch)),
                          train_s))
        t0 = time.perf_counter()
        shards, opt, loss = step(shards, opt, batch)
        losses.append(float(loss))        # the float() sync closes the step
        if i > 0:                         # step 0 = compile, off the clock
            train_s += time.perf_counter() - t0
        times.append(train_s)
        i += 1
        if train_s > seconds:
            break
        if i % 25 == 0:
            print(f"[moe-ab:{name}] step {i:4d} loss {losses[-1]:7.4f} "
                  f"t {train_s:5.0f}s"
                  + (f" drop {drops[-1][1]:.3f}" if drops else ""),
                  flush=True)
    final_eval = float(eval_loss(shards, eval_batch))
    tok_s = (len(losses) - 1) * bs * seq / train_s
    print(f"[moe-ab:{name}] done: {len(losses)} steps, "
          f"{tok_s:.0f} tok/s, final eval {final_eval:.4f}", flush=True)
    return {
        "name": name,
        "config": {k: (v if isinstance(v, (int, float, str, bool,
                                           type(None))) else str(v))
                   for k, v in cfg_overrides.items()},
        "seq": seq, "batch": bs,
        "seconds": times[-1], "steps": len(losses),
        "tokens_per_second": round(tok_s, 1),
        "final_eval_loss": final_eval,
        "losses": losses, "times": times,
        "evals": evals, "drop_trajectory": drops,
    }


def plot(out: dict, path: Path) -> None:
    import matplotlib
    matplotlib.use("Agg")
    import matplotlib.pyplot as plt

    fig, (a1, a2, a3) = plt.subplots(1, 3, figsize=(15, 4))
    for leg in out["legs"]:
        a1.plot(leg["times"], leg["losses"], lw=0.7, label=leg["name"])
        a2.plot([e[0] for e in leg["evals"]],
                [e[1] for e in leg["evals"]], marker="o", ms=2,
                label=leg["name"])
        if leg["drop_trajectory"]:
            a3.plot([d[0] for d in leg["drop_trajectory"]],
                    [d[1] for d in leg["drop_trajectory"]], marker="o",
                    ms=2, label=leg["name"])
    a1.set_xlabel("wall-clock s (post-compile)")
    a1.set_ylabel("train loss")
    a1.set_title("loss vs wall-clock (matched budget)")
    a2.set_xlabel("step"); a2.set_title("held-out eval loss")
    a3.set_xlabel("step"); a3.set_ylabel("drop fraction")
    a3.set_title("dispatch drop rate as router trains")
    for a in (a1, a2, a3):
        a.legend(fontsize=7)
    fig.tight_layout()
    path.parent.mkdir(parents=True, exist_ok=True)
    fig.savefig(path, dpi=120)
    print(f"[moe-ab] plot -> {path}")


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--seconds", type=float, default=420.0)
    p.add_argument("--sequence-length", type=int, default=8192)
    p.add_argument("--batch-size", type=int, default=4)
    p.add_argument("--peak-lr", type=float, default=3e-4)
    p.add_argument("--warmup-steps", type=int, default=30)
    p.add_argument("--eval-every", type=int, default=20)
    p.add_argument("--aux-weight", type=float, default=0.01,
                   help="MoE load-balance weight for the MoE legs — the "
                        "first A/B (default 0.01) measured the router "
                        "COLLAPSING (drop rate 0.10→0.65 as it trains); "
                        "re-run with 0.1 to test whether a stronger "
                        "balance loss rescues the throughput win")
    p.add_argument("--z-weight", type=float, default=0.0,
                   help="router z-loss weight (ST-MoE): keeps router "
                        "logits small so the balance aux keeps gradient "
                        "signal — the r5 router-health knob")
    p.add_argument("--router-lr-mult", type=float, default=1.0,
                   help="LR multiplier on w_router leaves (<1 slows the "
                        "router relative to the experts)")
    p.add_argument("--capacity-factors", type=float, nargs="+",
                   default=[2.0, 1.0],
                   help="one MoE leg per capacity factor")
    p.add_argument("--top-k", type=int, default=1,
                   help="experts per token (2 = GShard top-2: ~2x "
                        "active MLP FLOPs, usually better quality)")
    p.add_argument("--dense-from", default=None,
                   help="with --skip-dense: json file to read the dense "
                        "baseline eval from (default: the untagged "
                        "quality_ab_<platform>.json)")
    p.add_argument("--data", choices=["synthetic", "corpus"],
                   default="synthetic",
                   help="'corpus' = the committed real-text corpus "
                        "(data/corpus/, vocab 8192) — pair with "
                        "--geometry corpus-70m")
    p.add_argument("--geometry", default=None,
                   help="model registry name for the base geometry "
                        "(default: the 3B-L8 flagship; 'corpus-70m' for "
                        "real-text runs)")
    p.add_argument("--tag", default="",
                   help="suffix for the output json/plot (e.g. aux01)")
    p.add_argument("--skip-dense", action="store_true",
                   help="reuse an earlier run's dense leg (the dense "
                        "model has no aux knob)")
    p.add_argument("--cpu-devices", type=int, default=0)
    p.add_argument("--tiny", action="store_true",
                   help="CI shape: tiny geometry, short budget")
    p.add_argument("--out-dir", default="moe_results")
    p.add_argument("--plot", default="plots/moe_quality_ab.png")
    args = p.parse_args(argv)

    if args.skip_dense and not args.tag:
        raise SystemExit("--skip-dense needs --tag: without one the "
                         "output would overwrite the very file the "
                         "dense baseline is read from")
    if args.cpu_devices:
        from distributed_training_sandbox_tpu.utils import use_cpu_devices
        use_cpu_devices(args.cpu_devices)

    import jax
    from distributed_training_sandbox_tpu.data import make_packed_dataset
    from distributed_training_sandbox_tpu.models import (
        MODEL_REGISTRY, transformer as T)

    seq, bs = args.sequence_length, args.batch_size
    base = (MODEL_REGISTRY[args.geometry] if args.geometry
            else "SMOLLM3_3B_L8")
    moe = _base_moe()
    if base == "CORPUS_LM":
        # scale the expert width with the geometry: dense ffn / 4 keeps
        # the "dense MLP FLOPs split 4-ways active" shape of the 3B MoE
        moe = {**moe, "moe_ffn": T.CORPUS_LM.intermediate_size // 4}
    tiny_over = {}
    if args.tiny:
        seq, bs = 128, 4
        tiny_over = dataclasses.asdict(T.TINY_LM)
        moe = {**_base_moe(), "n_experts": 4, "moe_ffn": 40}

    vocab = (tiny_over or dataclasses.asdict(getattr(T, base)))["vocab_size"]
    if args.data == "corpus":
        root = Path(__file__).resolve().parent.parent
        ii, ll = make_packed_dataset(
            seq, vocab, source="corpus",
            corpus_path=root / "data" / "corpus" / "docstrings.txt",
            tokenizer_file=root / "data" / "corpus" / "tokenizer.json")
        print(f"[moe-ab] corpus: {len(ii)} windows of seq {seq}")
    else:
        # ~400 steps of fresh windows, looped if a leg outruns them
        n_tok = (400 * bs + 8) * (seq + 1)
        ii, ll = make_packed_dataset(seq, vocab, num_tokens=n_tok,
                                     source="synthetic", engine="native")
    import jax.numpy as jnp
    eval_batch = (jnp.asarray(ii[-8:]), jnp.asarray(ll[-8:]))
    data = (ii[:-8], ll[:-8])

    def with_tiny(over):
        return {**tiny_over, **over} if args.tiny else over

    aw, zw, rlm = args.aux_weight, args.z_weight, args.router_lr_mult
    health_tag = ("" if aw == 0.01 else f"_aux{aw:g}") \
        + (f"_z{zw:g}" if zw else "") + (f"_rlm{rlm:g}" if rlm != 1.0 else "") \
        + (f"_top{args.top_k}" if args.top_k != 1 else "")
    health = {"moe_aux_weight": aw, "moe_router_z_weight": zw,
              "moe_router_lr_mult": rlm, "moe_top_k": args.top_k}
    leg_list = [] if args.skip_dense else [("dense", {})]
    leg_list += [
        (f"moe_cf{cf:g}{health_tag}",
         {**moe, "moe_capacity_factor": cf, **health})
        for cf in args.capacity_factors
    ]
    legs = []
    for name, over in leg_list:
        legs.append(run_leg(name, with_tiny(over), args.seconds, seq, bs,
                            args.peak_lr, args.warmup_steps,
                            args.eval_every, data, eval_batch, base=base))

    if args.skip_dense:
        prior = Path(args.dense_from) if args.dense_from else (
            Path(args.out_dir)
            / f"quality_ab_{jax.devices()[0].platform}.json")
        dense_eval = json.loads(prior.read_text())["verdict"]["dense"][
            "final_eval_loss"] if prior.exists() else float("nan")
    else:
        dense_eval = legs[0]["final_eval_loss"]
    out = {
        "platform": jax.devices()[0].platform,
        "seconds_budget": args.seconds,
        "verdict": {
            leg["name"]: {
                "final_eval_loss": leg["final_eval_loss"],
                "delta_vs_dense": round(leg["final_eval_loss"]
                                        - dense_eval, 4),
                "tokens_per_second": leg["tokens_per_second"],
                "final_drop_rate": (leg["drop_trajectory"][-1][1]
                                    if leg["drop_trajectory"] else None),
            } for leg in legs
        },
        "legs": legs,
    }
    out_dir = Path(args.out_dir)
    out_dir.mkdir(exist_ok=True)
    tag = f"_{args.tag}" if args.tag else ""
    path = out_dir / f"quality_ab_{out['platform']}{tag}.json"
    path.write_text(json.dumps(out))
    print(f"[moe-ab] verdict: {json.dumps(out['verdict'], indent=1)}")
    print(f"[moe-ab] -> {path}")
    plot_path = Path(args.plot)
    if tag:
        plot_path = plot_path.with_name(
            plot_path.stem + tag + plot_path.suffix)
    plot(out, plot_path)


if __name__ == "__main__":
    main()
