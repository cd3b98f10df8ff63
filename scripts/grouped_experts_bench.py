"""Microbenchmark of the held experts' routed product on the chip, at the
shapes the serving cells 6, 8 and 9 give it (a decode step's rows and a
prefill chunk's): the masked (held experts x all rows) einsums that
``mla_moe.expert_mlp`` ran before PR 39, ``ops/grouped_experts.routed_sum``
as it stands, its plan alone, and the two library forms it was chosen over:
a buffer of pairs sorted by expert (an XLA sort and two XLA row gathers)
under ``megablox.gmm`` and under ``lax.ragged_dot``.

    chiprun -- python scripts/grouped_experts_bench.py

One jitted program chains ``--reps`` calls (each call's rows depend on
the last one's sum), so a host dispatch is paid once a program; the time
printed is the median program over ``--runs`` divided by ``--reps`` (it
holds ~0.15 ms a rep of the chain's own: ``plan_only`` reads that, the plan
itself is ~5 us of device time by a profile), beside one call's largest
difference from the masked sum over that sum's largest entry.  A line a
variant goes to ``chiprun_out/grouped_experts_bench.jsonl``.
Refuses to time anything but a TPU.
"""

from __future__ import annotations

import argparse
import functools
import json
import statistics
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
from jax import lax

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from distributed_training_sandbox_tpu.ops import grouped_experts as G  # noqa: E402
from jax.experimental.pallas.ops.tpu.megablox import gmm  # noqa: E402

#: name: (rows, held experts, H, F, experts a token, router width)
SHAPES = {
    "cell9.decode": (48, 8, 3072, 3072, 4, 256),
    "cell9.prefill": (512, 8, 3072, 3072, 4, 256),
    "cell8.decode": (64, 32, 2048, 512, 10, 512),
    "cell8.prefill": (256, 32, 2048, 512, 10, 512),
    "cell6.decode": (64, 8, 7680, 2048, 8, 256),
    "cell6.prefill": (256, 8, 7680, 2048, 8, 256),
    # every choice held here: the most pairs the buffer can hold
    "cell9.prefill.dense": (512, 8, 3072, 3072, 4, 8),
}


def masked(rows, w_held, wg, wu, wd):
    g = jnp.einsum("th,ehf->etf", rows, wg)
    u = jnp.einsum("th,ehf->etf", rows, wu)
    y = jnp.einsum("etf,efh->eth", jax.nn.silu(g) * u, wd,
                   preferred_element_type=jnp.float32)
    return jnp.einsum("eth,te->th", y, w_held)


def sorted_pairs(w_held, per_row):
    """The plan the library forms take: the pairs' rows sorted by expert
    in a buffer of T x per_row rows (whole 128-row tiles), group sizes,
    and each row's places in the buffer with their weights."""
    T, E = w_held.shape
    hit = w_held > 0
    sizes = jnp.sum(hit, axis=0, dtype=jnp.int32)
    _, row = lax.sort(
        (jnp.where(hit, jnp.arange(E, dtype=jnp.int32), E).reshape(-1),
         lax.broadcasted_iota(jnp.int32, (T, E), 0).reshape(-1)),
        num_keys=1, is_stable=True)
    P = -(-T * per_row // 128) * 128
    row = jnp.pad(row, (0, P - T * E)) if P > T * E else row[:P]
    place = jnp.where(hit, jnp.cumsum(sizes) - sizes
                      + jnp.cumsum(hit, axis=0, dtype=jnp.int32) - 1, P)
    place, share = lax.sort((place, jnp.where(hit, w_held, 0.0)),
                            dimension=1, num_keys=1)
    return row, sizes, place[:, :per_row], share[:, :per_row]


def library(mm, per_row):
    """The routed sum over ``sorted_pairs`` with ``mm(lhs, rhs, sizes,
    out_dtype)`` as its grouped matmul: an XLA gather of the pairs' rows,
    three grouped matmuls, an XLA gather of each row's pairs."""
    def f(rows, w_held, wg, wu, wd):
        row, sizes, place, share = sorted_pairs(w_held, per_row)
        x = rows[row]
        h = jax.nn.silu(mm(x, wg, sizes, rows.dtype)) \
            * mm(x, wu, sizes, rows.dtype)
        y = mm(h, wd, sizes, jnp.float32)
        mine = y[jnp.minimum(place, y.shape[0] - 1)]
        return jnp.sum(jnp.where((share > 0)[:, :, None],
                                 mine * share[:, :, None], 0.0), axis=1)
    return f


def megablox(a, w, s, dt):
    """``megablox.gmm`` with whole rows of the matrix a block (up to 3,072
    columns) and as much of the contraction as 4 MB of bf16 hold: the best
    of the eight tilings PR 39's first call tried, all within 8%."""
    k, n = w.shape[1:]
    tn = max(d for d in range(128, min(n, 3072) + 1, 128) if n % d == 0)
    tk = max(d for d in range(128, k + 1, 128)
             if k % d == 0 and d * tn <= 2 ** 21)
    return gmm(a, w, s, preferred_element_type=dt, tiling=(128, tk, tn))


def ragged(a, w, s, dt):
    return lax.ragged_dot(a, w, s, preferred_element_type=jnp.float32
                          ).astype(dt)


def plan_only(per_row):
    """``plan_visits`` and a consumer of every array it returns: what the
    plan costs a layer beside the kernel."""
    def f(rows, w_held, wg, wu, wd):
        s = sum(jnp.sum(a).astype(jnp.float32)
                for a in G.plan_visits(w_held, None, per_row))
        return jnp.zeros(rows.shape, jnp.float32) + s * 1e-9
    return f


def retiled(per_row, **rules):
    """``routed_sum`` traced with other tile rules in the module (the
    caller clears jit's caches: a rule is no part of a cache key)."""
    def f(*a):
        kept = {k: getattr(G, k) for k in rules}
        for k, rule in rules.items():
            setattr(G, k, functools.partial(rule, kept[k]))
        try:
            return G.routed_sum(*a, per_row=per_row)
        finally:
            for k, rule in kept.items():
                setattr(G, k, rule)
    return f


def chained(f, reps):
    def prog(rows, w_held, wg, wu, wd):
        for _ in range(reps):
            rows = rows + (f(rows, w_held, wg, wu, wd) * 1e-3
                           ).astype(rows.dtype)
        return rows
    return jax.jit(prog)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=4)
    ap.add_argument("--runs", type=int, default=5)
    ap.add_argument("--shapes", nargs="*", default=list(SHAPES))
    ap.add_argument("--variants", nargs="*", help="default: all")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        sys.exit("grouped_experts_bench times a TPU only")
    out = ROOT / "chiprun_out" / "grouped_experts_bench.jsonl"
    out.parent.mkdir(exist_ok=True)
    for name in args.shapes:
        T, E, H, F, k, width = SHAPES[name]
        ks = jax.random.split(jax.random.key(args.seed), 6)
        rows = jax.random.normal(ks[0], (T, H), jnp.bfloat16)
        wg, wu = (jax.random.normal(kk, (E, H, F), jnp.bfloat16) * H ** -.5
                  for kk in ks[1:3])
        wd = jax.random.normal(ks[3], (E, F, H), jnp.bfloat16) * F ** -.5
        top, idx = lax.top_k(jax.random.uniform(ks[4], (T, width)), k)
        w_held = jnp.sum(jnp.where(
            idx[:, :, None] == jnp.arange(E)[None, None], top[:, :, None],
            0.0), axis=1)
        per_row = min(k, E)
        pairs = int(jnp.sum(w_held > 0))
        touched = int(jnp.sum(jnp.any(w_held > 0, axis=0)))
        variants = {
            "masked": masked,
            "grouped": lambda *a: G.routed_sum(*a, per_row=per_row),
            "plan_only": plan_only(per_row),
            "megablox_gmm": library(megablox, per_row),
            "ragged_dot": library(ragged, per_row),
            "grouped.half_width_block": retiled(
                per_row, width_block=lambda was, h, w: max(128, was(h, w) // 2)),
            "grouped.half_row_tile": retiled(
                per_row, row_tile=lambda was, r: max(16, was(r) // 2)),
        }
        data = (rows, w_held, wg, wu, wd)
        want = jax.jit(masked)(*data)
        for vname, f in variants.items():
            if args.variants and vname not in args.variants:
                continue
            rec = {"shape": name, "variant": vname, "rows": T, "held": E,
                   "pairs": pairs, "touched": touched}
            try:
                jax.clear_caches()
                if vname != "plan_only":    # one call against the masked sum
                    rec["max_err_over_max"] = float(
                        jnp.max(jnp.abs(jax.jit(f)(*data) - want))
                        / jnp.max(jnp.abs(want)))
                prog = chained(f, args.reps)
                t0 = time.perf_counter()
                prog(*data).block_until_ready()  # sync-ok
                rec["compile_s"] = round(time.perf_counter() - t0, 2)
                took = []
                for _ in range(args.runs):
                    t0 = time.perf_counter()
                    prog(*data).block_until_ready()  # sync-ok
                    took.append(time.perf_counter() - t0)
                rec["ms"] = round(statistics.median(took) / args.reps * 1e3,
                                  4)
            except Exception as e:  # noqa: BLE001  what Mosaic refuses
                rec["error"] = f"{type(e).__name__}: {str(e)[:200]}"
            print(json.dumps(rec), flush=True)
            with out.open("a") as fh:
                fh.write(json.dumps(rec) + "\n")


if __name__ == "__main__":
    main()
