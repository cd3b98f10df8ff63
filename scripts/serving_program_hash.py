#!/usr/bin/env python3
"""Whether a change left the benchmark's serving programs as they were:
sha256 of each serving configuration's decode step and prefill chunk,
lowered FOR a TPU on this host (no chip), at the cell's shapes.

    JAX_PLATFORMS=cpu python scripts/serving_program_hash.py [--root DIR]

prints one line a program (``--root`` another checkout of THIS program;
for a parent whose programs take other arguments run the parent's own copy
of this script, which lowers what its engine launches, and compare).  The
hash is of the StableHLO with every Mosaic kernel's serialized module
replaced by the hash of its MLIR printed WITHOUT debug locations: a Pallas
kernel's payload embeds the source lines of the kernel and of every caller
up to the engine, so an edit that shifts a line in ``serving/engine.py``
would change the hash of every program as lowered.  Equal hashes mean the
same ops and the same kernels.
"""

from __future__ import annotations

import argparse
import base64
import hashlib
import inspect
import json
import os
import re
import sys
from pathlib import Path

_BODY = re.compile(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22')


def canonical(text: str) -> str:
    """``text`` with each Mosaic payload replaced by the hash of its module
    printed without debug locations."""
    from jax._src.interpreters import mlir as jmlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir
    ctx = jmlir.make_ir_context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True

    def sub(m):
        with ctx:
            asm = ir.Module.parse(base64.b64decode(m.group(1))) \
                .operation.get_asm(enable_debug_info=False)
        return '\\22body\\22: \\22<' + _sha(asm) + '>\\22'

    return _BODY.sub(sub, text)


def _sha(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()[:12]


def programs(root: Path):
    """(cell, program, lowered text) of every serving cell under ``root``."""
    sys.path.insert(0, str(root))
    import jax
    import jax.numpy as jnp
    from benchmarks import harness
    from distributed_training_sandbox_tpu.models import transformer as T
    from distributed_training_sandbox_tpu.serving import engine as E
    from distributed_training_sandbox_tpu.serving.kv_pool import (
        PagedKVPool, layer_kinds, ring_pages)
    jax.default_backend = lambda: "tpu"     # the engine's kernels, as there
    sd = jax.ShapeDtypeStruct
    i32 = lambda *s: sd(s, jnp.int32)  # noqa: E731
    sync_default = inspect.signature(
        E.ServingEngine).parameters["sync_every"].default
    for row in harness.load_benchmark(root)["workloads"]:
        cell = harness.load_cell(row["name"], root)
        if cell.runner != "serve":
            continue
        mcfg = harness.model_config(cell.config["fields"])
        eng = cell.traffic["engine"]
        B, page = eng["max_batch"], eng["page_size"]
        P = -(-eng["max_seq_len"] // page)
        # the tree the engine's programs read (the dense block's holds
        # its fused q, k, v leaves beside the caller's)
        params = jax.eval_shape(lambda: E._dense_serving_tree(
            T.init_params(jax.random.key(0), mcfg), E._decode_cfg(mcfg)))
        # the facts the engine reads: state slots beside pages, a second
        # page class (a ring a slot, a second table), latent rows
        kinds = layer_kinds(mcfg)
        slots = {"n_slots": B} if mcfg.state_slots else {}
        tables = lambda b: i32(b, P)  # noqa: E731
        if "window" in kinds:
            R = ring_pages(mcfg, page, eng["prefill_chunk"])
            slots = {"n_pages_window": B * R + 1}
            tables = lambda b: (i32(b, P), i32(b, R))  # noqa: E731
        bufs = jax.eval_shape(
            lambda: PagedKVPool(mcfg, B * P + 1, page, **slots).bufs)
        # the burst's carry, as the engine sizes it (a configuration may
        # set ``sync_every``)
        sync = cell.config["serve"]["engine"].get("sync_every", sync_default)
        decode = E.make_serve_decode_step(mcfg, paged_kernel=True).trace(
            bufs, params, tables(B), i32(B), i32(B), i32(B),
            sd((B,), jnp.bool_), i32(len(E.device_counters(mcfg)) + sync * B))
        prefill = E.make_serve_prefill_step(
            mcfg, paged_kernel="latent" not in kinds).trace(
            bufs, params, tables(1), i32(1, eng["prefill_chunk"]), i32(),
            i32(), *((i32(),) if mcfg.state_slots else ()))
        for name, traced in (("decode", decode), ("prefill", prefill)):
            yield cell.name, name, traced.lower(
                lowering_platforms=("tpu",)).as_text()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--root", type=Path,
                    default=Path(__file__).resolve().parents[1])
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for cell, name, text in programs(args.root.resolve()):
        print(json.dumps({"cell": cell, "program": name,
                          "canonical": _sha(canonical(text))}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
