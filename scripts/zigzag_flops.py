"""Zigzag-vs-contiguous ring attention: compiled FLOP comparison.

The zigzag layout's win is per-hop USEFUL work: every remote hop runs two
fully-visible W×W stripe products instead of one masked S_local² block,
so the ring's score/AV FLOPs roughly halve (``ops/ring_attention.py``
module docstring).  The wall-clock win needs a ring of several chips and
is not measured — what IS measurable anywhere, exactly, is
the compiled step's FLOP count on the 8-device CPU-sim mesh via XLA's
``compiled.cost_analysis()``.  This script compiles the SAME dp×sp train
step under both layouts and reports total step FLOPs + the implied ring
reduction, writing ``longcontext_results/zigzag_flops_<platform>.json``.

    python scripts/zigzag_flops.py [--seq 8192] [--layers 2]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def step_flops(layout: str, seq: int, layers: int, mesh, sp: int) -> float:
    import jax
    import jax.numpy as jnp
    from distributed_training_sandbox_tpu.models import transformer as T
    from distributed_training_sandbox_tpu.parallel import fsdp, sequence

    cfg = dataclasses.replace(
        T.SMOLLM3_350M, num_hidden_layers=layers, remat=False)
    cfg = sequence.sp_config(cfg, "sp", layout=layout)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    shards = fsdp.shard_params_fsdp(params, mesh, "dp")
    opt = fsdp.init_fsdp_opt_state(shards)
    step = fsdp.make_fsdp_train_step(shards, cfg, mesh, axis="dp",
                                     sp_axis="sp", donate=False)
    ids = jnp.zeros((2, seq), jnp.int32)
    compiled = step.lower(shards, opt, (ids, ids)).compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):   # per-device list on some backends
        cost = cost[0]
    return float(cost["flops"])


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--seq", type=int, default=8192)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--out-dir", default="longcontext_results")
    args = p.parse_args(argv)

    from distributed_training_sandbox_tpu.utils import use_cpu_devices
    use_cpu_devices(8)
    import jax
    import numpy as np
    from jax.sharding import Mesh

    sp = 4
    mesh = Mesh(np.array(jax.devices()).reshape(2, sp), ("dp", "sp"))
    f_contig = step_flops("contiguous", args.seq, args.layers, mesh, sp)
    f_zigzag = step_flops("zigzag", args.seq, args.layers, mesh, sp)
    saved = f_contig - f_zigzag
    row = {
        "platform": jax.devices()[0].platform,
        "mesh": f"2x{sp} (dp x sp)", "seq": args.seq,
        "layers": args.layers,
        "step_flops_contiguous": f_contig,
        "step_flops_zigzag": f_zigzag,
        "flops_saved_pct_of_step": round(100 * saved / f_contig, 2),
        "note": ("exact XLA cost_analysis of the identical dp×sp train "
                 "step; the delta is the ring's computed-then-masked "
                 "score/AV work the zigzag layout never computes.  "
                 "Wall-clock effect needs a real multi-chip slice: "
                 "not measured."),
    }
    print(f"[zigzag-flops] contiguous {f_contig:.3e}  "
          f"zigzag {f_zigzag:.3e}  saved {row['flops_saved_pct_of_step']}"
          f"% of total step FLOPs", flush=True)
    out = Path(args.out_dir)
    out.mkdir(exist_ok=True)
    path = out / f"zigzag_flops_{jax.devices()[0].platform}.json"
    path.write_text(json.dumps(row, indent=1))
    print(f"[zigzag-flops] wrote {path}")


if __name__ == "__main__":
    main()
