"""Long-context single-chip sweep: training past the reference's ceiling.

The reference's longest trained sequence is 8192 (its fp8 sweep grid caps
there, ``fp8/modal_app.py:90``; SURVEY.md §5.7).  This sweep runs the
flagship FSDP train step (AdamW, fused splash attention, streamed-vocab
loss, full remat) at 16k/32k/64k on one chip — the combination of
O(S)-memory attention and the spike-free loss is exactly what makes
these lengths reachable at all (``scripts/memory_waterline.py``: the
dense-loss design already fails to fit at 8192×2).

Writes ``longcontext_results/longcontext_<platform>.json`` (one row per
seq, same schema as bench.py's matrix rows) and prints a markdown table.

    python scripts/long_context.py [--model SMOLLM3_3B_L8] [--steps 6]
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import bench  # noqa: E402  (repo-root benchmark harness)

SEQS = (8192, 16384, 32768, 65536)


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="SMOLLM3_3B_L8")
    p.add_argument("--steps", type=int, default=6)
    p.add_argument("--out-dir", default="longcontext_results")
    p.add_argument("--seqs", type=int, nargs="*", default=list(SEQS))
    args = p.parse_args(argv)

    import jax
    rows = []
    for seq in args.seqs:
      for precision in ("bf16", "int8_bwd"):
        # The streamed-loss chunk buffer is B·S·chunk fp32 — at 64k the
        # default 16032-row chunk alone is ~4.2 GB (doesn't fit next to
        # the activations), so extreme lengths use a narrower chunk
        # (more scan steps, same math).  int8_bwd has the same residency
        # as bf16 (custom-vjp residuals are (x, w) either way) and moved
        # the 64k row 83.5 -> 94.4 TFLOPS in r3; remat alternatives
        # (save_attn even at a halved loss chunk) OOM at 17.08/15.75 GB.
        over = {"loss_vocab_chunk": 4008} if seq > 32768 else {}
        if precision != "bf16":
            over = {**over, "matmul_precision": precision}
        try:
            r = bench.measure(args.model, seq, 1, num_steps=args.steps,
                              cfg_overrides=over)
            rows.append({**r, **({"config": over} if over else {})})
        except Exception as e:
            rows.append({"model": args.model, "seq_len": seq, "batch": 1,
                         "config": over,
                         "error": f"{type(e).__name__}: {str(e)[:160]}"})
        print(f"[longctx] {rows[-1]}", flush=True)

    platform = jax.devices()[0].platform
    out = Path(args.out_dir)
    out.mkdir(exist_ok=True)
    path = out / f"longcontext_{platform}.json"
    path.write_text(json.dumps(rows, indent=1))

    print("\n| seq | precision | tok/s | step ms | TFLOPS/device |"
          "\n|---|---|---|---|---|")
    for r in rows:
        prec = r.get("config", {}).get("matmul_precision", "bf16")
        if "error" in r:
            print(f"| {r['seq_len']} | {prec} | — | — | "
                  f"{r['error'][:60]} |")
        else:
            print(f"| {r['seq_len']} | {prec} | {r['tokens_per_sec']:.0f} "
                  f"| {r['step_ms']:.0f} | {r['tflops_per_device']:.2f} |")
    print(f"\n[longctx] wrote {path}")


if __name__ == "__main__":
    main()
