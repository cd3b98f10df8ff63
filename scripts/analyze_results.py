"""Post-hoc results analysis — the committed twin of the reference's
``fp8/visualize_code.ipynb`` (cells 1, 7-10: regex-harvest run logs →
pandas → TFLOPS / tok/s comparison plots).

Reads the machine-readable artifacts the benchmark scripts write —
``precision_results/summary_*.json`` (precision sweeps) and
``pp_results/*.json`` (GPipe/1F1B runs) — and regenerates comparison
tables (tok/s, TFLOPS/device, peak memory by model × seq × precision;
schedule metrics for pp) as one markdown report.  One command, committed
inputs, reproducible output:

  python scripts/analyze_results.py [--precision-dir precision_results]
      [--pp-dir pp_results] [--out RESULTS.md]
"""

from __future__ import annotations

import argparse
import glob
import json
import sys
from pathlib import Path

# Prepend the checkout root so the source tree always wins over any
# installed copy of the package (`pip install -e .` makes this a no-op).
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def _load_json_rows(dirname: str, pattern: str = "*.json") -> list[dict]:
    """Concatenate row dicts from every matching JSON file (each file may
    hold a list of rows or a single row object)."""
    rows = []
    for f in sorted(glob.glob(f"{dirname}/{pattern}")):
        d = json.load(open(f))
        if isinstance(d, dict) and "rows" in d:   # wrapped artifact
            d = d["rows"]
        for r in (d if isinstance(d, list) else [d]):
            if isinstance(r, dict):
                r.setdefault("_file", Path(f).stem)
        rows.extend(d if isinstance(d, list) else [d])
    return rows


def load_precision(dirname: str) -> tuple[list[dict], list[dict]]:
    """(measured rows, failure rows).  Last write wins per
    (model, precision, seq, devices, batch) — the r4 sweeps carry a
    batch dimension (VERDICT r3 #2: batch-1 defaults understated every
    family)."""
    rows = _load_json_rows(dirname, "summary_*.json")
    dedup, fails = {}, {}
    # files glob in timestamp order, so iteration is oldest -> newest:
    # the newest verdict for a key wins ACROSS the two buckets too (a
    # config that OOM'd once but succeeds after a fix must not be
    # published as both a result and an edge).
    for r in rows:
        key = (r["model"], r["precision"], r["sequence_length"],
               r.get("num_devices", 1), r.get("batch_size"))
        if "failure" in r or "error" in r:
            fails[key] = r
            dedup.pop(key, None)
        else:
            dedup[key] = r
            fails.pop(key, None)
    return list(dedup.values()), list(fails.values())


def best_by_batch(rows: list[dict]) -> list[dict]:
    """Collapse the batch dimension: per (model, precision, seq,
    devices) keep the best-throughput batch, remembering it in
    ``best_batch``."""
    best: dict = {}
    for r in rows:
        key = (r["model"], r["precision"], r["sequence_length"],
               r.get("num_devices", 1))
        if key not in best or (r["tokens_per_second"]
                               > best[key]["tokens_per_second"]):
            best[key] = {**r, "best_batch": r.get("batch_size")}
    return list(best.values())


def precision_tables(all_rows: list[dict], fails: list[dict]) -> str:
    if not all_rows:
        return "_no precision summaries found_\n"
    rows = best_by_batch(all_rows)
    models = sorted({r["model"] for r in rows})
    seqs = sorted({r["sequence_length"] for r in rows})
    devs = sorted({r["num_devices"] for r in rows})
    precisions = list(dict.fromkeys(r["precision"] for r in rows))
    by = {(r["model"], r["precision"], r["sequence_length"],
           r["num_devices"]): r for r in rows}
    out = ["Each cell is that configuration's BEST measured batch "
           "(the `@bN` tag; batch swept 1/2/4/8 to the OOM edge — "
           "VERDICT r3 #2's re-calibration of the old batch-1 rows).\n"]
    for metric, fmt, title in (
            ("tokens_per_second", "{:.0f}", "tokens/sec"),
            ("tflops_per_device", "{:.2f}", "TFLOPS/device"),
    ):
        out.append(f"### {title}\n")
        header = "| model | seq | devices | " + " | ".join(precisions) \
            + " | best int8 vs bf16 |"
        out += [header, "|" + "---|" * (len(precisions) + 4)]
        for m in models:
            for s in seqs:
                for d in devs:
                    vals = {p: by.get((m, p, s, d)) for p in precisions}
                    if not any(vals.values()):
                        continue
                    cells = [m, str(s), str(d)]
                    cells += [
                        (fmt.format(vals[p][metric])
                         + (f" @b{vals[p]['best_batch']}"
                            if vals[p].get("best_batch") else ""))
                        if vals[p] else "—"
                        for p in precisions]
                    ints = [vals[p][metric] for p in precisions
                            if p != "bf16" and vals[p]]
                    if vals.get("bf16") and vals["bf16"][metric] and ints:
                        speedup = max(ints) / vals["bf16"][metric] - 1.0
                        cells.append(f"{speedup:+.1%}")
                    else:
                        cells.append("—")
                    out.append("| " + " | ".join(cells) + " |")
        out.append("")
    out.append("### memory at the best batch (compile plan = argument "
               "buffers + XLA temps, GB — outputs alias the donated "
               "args; model + optimizer MB per device)\n")
    out += ["| model | seq | devices | precision | best batch | plan GB "
            "| model MB | optimizer MB |",
            "|---|---|---|---|---|---|---|---|"]
    for m in models:
        for s in seqs:
            for d in devs:
                for p in precisions:
                    r = by.get((m, p, s, d))
                    if r:
                        pm = r.get("peak_memory", {})
                        plan = pm.get("memory_plan_gb")
                        if (plan is not None
                                and pm.get("plan_formula") != "args+temps"):
                            # older artifacts counted donated outputs on
                            # top of the argument buffers they alias —
                            # subtract the (model + optimizer) state once
                            plan = round(plan - (pm.get("model_mb", 0)
                                         + pm.get("optimizer_mb", 0))
                                         / 1024, 2)
                        out.append(
                            f"| {m} | {s} | {d} | {p} | "
                            f"{r.get('best_batch', '—')} | "
                            f"{plan if plan is not None else '—'} | "
                            f"{pm.get('model_mb', 0):.0f} | "
                            f"{pm.get('optimizer_mb', 0):.0f} |")
    out.append("")
    if fails:
        out.append("### OOM edges (XLA's own verdict; non-OOM failures "
                   "are never published as edges)\n")
        out += ["| model | seq | precision | batch | kind |",
                "|---|---|---|---|---|"]
        for r in sorted(fails, key=lambda r: (r["model"],
                                              r["sequence_length"],
                                              r["precision"],
                                              r.get("batch_size") or 0)):
            out.append(f"| {r['model']} | {r['sequence_length']} | "
                       f"{r['precision']} | {r.get('batch_size', '—')} | "
                       f"{r.get('failure', 'error')} |")
        out.append("")
    return "\n".join(out)


def load_longctx(dirname: str) -> list[dict]:
    # throughput rows only (the dir also holds side artifacts, e.g. the
    # zigzag FLOP-count comparison)
    return [r for r in _load_json_rows(dirname) if "model" in r]


def longctx_table(rows: list[dict]) -> str:
    if not rows:
        return "_no long-context sweep found_\n"
    out = ["| model | platform | seq | tok/s | step ms | TFLOPS/device "
           "| note |",
           "|---|---|---|---|---|---|---|"]
    for r in rows:
        note = "; ".join(f"{k}={v}" for k, v in
                         r.get("config", {}).items()) or ""
        plat = r.get("platform", "?")
        if "error" in r:
            out.append(f"| {r['model']} | {plat} | {r['seq_len']} "
                       f"| — | — | — | {r['error'][:60]} |")
        else:
            out.append(f"| {r['model']} | {plat} | {r['seq_len']} | "
                       f"{r['tokens_per_sec']:.0f} | {r['step_ms']:.0f} | "
                       f"{r['tflops_per_device']:.2f} | {note} |")
    out.append("")
    return "\n".join(out)


def decode_table(rows: list[dict]) -> str:
    if not rows:
        return "_no decode benchmark found_\n"
    out = ["Decode is read-bound: the roofline column is "
           "`(weight_bytes + KV_cache_bytes) / HBM bandwidth` per step "
           "(r5: the KV term was previously omitted, flattering short "
           "prompts).  int8 rows store weights AS int8 "
           "(`quantize_decode_params`); `+kvq` rows also store the KV "
           "cache int8 — both lower the floor itself.\n",
           "| model | precision | batch | prompt | new | weight GiB | "
           "KV GiB | steady tok/s | ms/step | roofline ms | "
           "roofline frac | prefill+1 s | status |",
           "|---|---|---|---|---|---|---|---|---|---|---|---|---|"]
    for r in rows:
        if "failure" in r or "error" in r:
            # failure kind goes in the dedicated status column, not in a
            # mislabeled data cell (r4 advisor)
            out.append(f"| {r['model']} | {r.get('precision', '—')} | "
                       f"{r.get('batch', '—')} | {r.get('prompt_len', '—')}"
                       f" | — | — | — | — | — | — | — | — | "
                       f"{r.get('failure', 'error')} |")
            continue
        roofline = r.get("read_roofline_ms_per_step",
                         r.get("weight_read_roofline_ms_per_step", "—"))
        out.append(
            f"| {r['model']} | {r.get('precision', 'bf16')} | "
            f"{r['batch']} | {r['prompt_len']} | {r['new_tokens']} | "
            f"{r.get('weight_gib', '—')} | "
            f"{r.get('kv_cache_gib', '—')} | "
            f"{r.get('steady_decode_tokens_per_sec', '—')} | "
            f"{r.get('steady_ms_per_step', r.get('steady_ms_per_token_per_seq', '—'))} | "
            f"{roofline} | "
            f"{r.get('roofline_fraction', '—')} | "
            f"{r.get('prefill_plus_1_s', '—')} | ok |")
    out.append("")
    return "\n".join(out)


def moe_drop_note(dirname: str) -> str:
    """Grouped-dispatch drop rates from the bench artifact (written
    next to the rows it describes)."""
    drops = []
    for f in sorted(glob.glob(f"{dirname}/*.json")):
        d = json.load(open(f))
        if isinstance(d, dict):
            drops += d.get("drop_rates_at_init", [])
    if not drops:
        return ""
    parts = [f"k{d.get('top_k', 1)}/cf{d['capacity_factor']} "
             f"{100 * d['drop_fraction']:.1f}%" for d in drops]
    return ("  Grouped drop rates at init (group "
            f"{drops[0]['group_size']}): " + ", ".join(parts) + ".")


def moe_table(rows: list[dict]) -> str:
    if not rows:
        return "_no MoE benchmark found_\n"
    out = ["| model | platform | seq | batch | dispatch | cf | k | "
           "precision | tok/s | TFLOPS/device (active) |",
           "|---|---|---|---|---|---|---|---|---|---|"]
    for r in rows:
        if "tflops_per_device" not in r and "error" not in r:
            continue   # e.g. phase-breakdown / drop-rate side artifacts
        c = r.get("config", {})
        disp = c.get("moe_dispatch", "?")
        cf = c.get("moe_capacity_factor", 2.0)
        k = c.get("moe_top_k", 1)
        prec = c.get("matmul_precision", "bf16")
        plat = r.get("platform", "?")
        if "error" in r:
            out.append(f"| {r['model']} | {plat} | {r['seq_len']} | "
                       f"{r['batch']} | {disp} | {cf} | {k} | {prec} | "
                       f"— | {r['error'][:50]} |")
        else:
            out.append(f"| {r['model']} | {plat} | {r['seq_len']} | "
                       f"{r['batch']} | {disp} | {cf} | {k} | {prec} | "
                       f"{r['tokens_per_sec']:.0f} | "
                       f"{r['tflops_per_device']:.2f} |")
    out.append("")
    return "\n".join(out)


def load_pp(dirname: str) -> list[dict]:
    return [r for r in _load_json_rows(dirname) if "schedule" in r]


def pp_table(rows: list[dict]) -> str:
    if not rows:
        return "_no pp result JSONs found_\n"
    out = ["| run | schedule | stages | micro | final loss | avg epoch s | "
           "epochs/s | mem/stage MB | max stored acts | "
           "act MB/microbatch | bubble |",
           "|---|---|---|---|---|---|---|---|---|---|---|"]
    for r in rows:
        # allocator peaks when available, else the compile-time plan
        # (memory_source tags which; this substrate exposes no runtime
        # allocator stats, so the plan is the honest number)
        mem = (r["peak_memory_mb"]
               if r.get("memory_source", "allocator") == "allocator"
               and any(r.get("peak_memory_mb", {}).values())
               else r.get("memory_plan_mb", {}))
        fmt = lambda d: "/".join(f"{v:.0f}" for v in d.values()) \
            if d else "—"
        stats = r.get("schedule_stats") or {}
        bubble = stats.get("bubble_fraction")
        stages = r.get("n_stages") or len(r.get("memory_plan_mb", {})) \
            or "—"
        if stats.get("v"):
            stages = (f"{stats['n_devices']}dev×{stats['v']}v")
        out.append(
            f"| {r.get('_file', '—')} "
            f"| {r['schedule']} | {stages} | {r.get('n_micro') or '—'} | "
            f"{r['final_loss']:.4f} | "
            f"{r['avg_epoch_time_s']:.3f} | {r['epochs_per_s']:.2f} | "
            f"{fmt(mem)}"
            f"{'' if r.get('memory_source', 'allocator') == 'allocator' else ' (plan)'} | "
            f"{fmt(r.get('max_stored_activations', {}))} | "
            f"{'/'.join(str(v) for v in r.get('activation_mb_per_microbatch', {}).values()) or '—'} | "
            f"{bubble if bubble is not None else '—'} |")
    out.append("")
    return "\n".join(out)


def flagship_section(dirname: str = "flagship_results") -> str:
    runs = _load_json_rows(dirname)
    if not runs:
        return "_no flagship training runs found_\n"
    out = ["Long-horizon proof that training *learns* (VERDICT r3 #1): "
           "every-step loss series with warmup+cosine LR; the no-warmup "
           "leg pins the cold-Adam early-step spike the schedule kills. "
           "Full series + plot: `flagship_results/`, "
           "`plots/flagship_loss.png`.\n",
           "| model | precision | seq | batch | steps | warmup | "
           "loss first | max(first 20) | final (mean last 20) | tok/s |",
           "|---|---|---|---|---|---|---|---|---|---|"]
    for r in sorted(runs, key=lambda r: (r["precision"],
                                         r["warmup_steps"])):
        out.append(
            f"| {r['model']} | {r['precision']} | {r['sequence_length']} "
            f"| {r['batch_size']} | {r['num_steps']} | "
            f"{r['warmup_steps'] or '—'} | {r['loss_first']:.3f} | "
            f"{r['loss_max_first20']:.3f} | "
            f"{r['loss_final_mean20']:.3f} | "
            f"{r['tokens_per_second']:.0f} |")
    out.append("")
    return "\n".join(out)


def moe_quality_section(dirname: str = "moe_results") -> str:
    rows = []
    for f in sorted(glob.glob(f"{dirname}/quality_ab_*.json")):
        rows.append(json.load(open(f)))
    if not rows:
        return ""
    out = ["## MoE quality A/B (`scripts/moe_quality_ab.py`)",
           "",
           "Dense vs MoE cf 2.0 vs cf 1.0 at MATCHED wall-clock, same "
           "seeded stream, warmup+cosine — the quality evidence behind "
           "the MoE throughput headline (VERDICT r3 #1c).  Drop rate is "
           "measured with the dispatch's own capacity rule on the LIVE "
           "router every eval step.  Plot: `plots/moe_quality_ab.png`.",
           ""]
    for d in rows:
        out += [f"Platform {d['platform']}, budget "
                f"{d['seconds_budget']:.0f}s per leg:", "",
                "| leg | steps | tok/s | final eval loss | Δ vs dense | "
                "final drop rate |",
                "|---|---|---|---|---|---|"]
        legs = {leg["name"]: leg for leg in d["legs"]}
        for name, v in d["verdict"].items():
            drop = v.get("final_drop_rate")
            out.append(
                f"| {name} | {legs[name]['steps']} | "
                f"{v['tokens_per_second']:.0f} | "
                f"{v['final_eval_loss']:.4f} | "
                f"{v['delta_vs_dense']:+.4f} | "
                f"{f'{drop:.3f}' if drop is not None else '—'} |")
        out.append("")
    # verdict COMPUTED from the rows just rendered (never asserted):
    # best MoE delta vs dense + per-leg drop trajectories
    moe_vs = []
    drops = []
    for d in rows:
        for name, v in d["verdict"].items():
            if name != "dense":
                moe_vs.append((v["delta_vs_dense"], name))
        for leg in d["legs"]:
            t = leg["drop_trajectory"]
            if t:
                drops.append(f"{leg['name']} {t[0][1]:.2f}→{t[-1][1]:.2f}")
    if moe_vs:
        best_delta, best_name = min(moe_vs)
        wins = best_delta < 0
        out += [
            ("**Verdict (computed from the tables above):** "
             + (f"the best MoE leg ({best_name}) beats dense by "
                f"{-best_delta:.4f} eval loss at matched wall-clock."
                if wins else
                f"NO measured MoE configuration beats dense at matched "
                f"wall-clock — the best ({best_name}) ends "
                f"{best_delta:+.4f} behind.  The MoE throughput "
                f"headline stands as a SYSTEMS result (dispatch "
                f"efficiency), not a quality win.")
             + "  Drop-rate trajectories (first→last as the router "
             "trains): " + "; ".join(drops) + ".  Scope caveat: the "
             "synthetic Zipf stream has essentially unigram structure "
             "— nothing for experts to specialize on — so this "
             "measures training-system mechanics (drop dynamics, "
             "aux-weight sensitivity), not MoE's ceiling on real "
             "text."),
            ""]
    return "\n".join(out)


def overlap_section(path: str = "ddp_results/overlap_analysis.json") -> str:
    try:
        d = json.load(open(path))
    except OSError:
        return ""
    out = ["## FSDP gather-schedule shapes "
           "(`scripts/overlap_analysis.py`)",
           "",
           "Where the compiled schedules put the per-layer gathers "
           "(in-loop re-gather = ZeRO-3, hoisted = ZeRO-2) and whether "
           "the in-loop operands are loop-invariant (the prefetchable "
           "shape XLA:TPU's collective pipeliner overlaps).  Full "
           f"verdict: `{path}`.",
           ""]
    for s in d.get("schedule_shapes", []):
        out.append(f"* {s}")
    out.append("")
    return "\n".join(out)


# Chart style: the validated reference palette (dataviz skill) — fixed
# categorical slot order, light surface, recessive grid, one axis.
_SURFACE = "#fcfcfb"
_INK, _INK2 = "#0b0b0b", "#52514e"
_SERIES = ["#2a78d6", "#eb6834", "#1baf7a", "#eda100", "#e87ba4"]


def _style_axes(ax):
    for s in ("top", "right"):
        ax.spines[s].set_visible(False)
    for s in ("left", "bottom"):
        ax.spines[s].set_color("#d6d5d1")
    ax.tick_params(colors=_INK2, labelsize=9)
    ax.yaxis.grid(True, color="#ececea", linewidth=0.8)
    ax.set_axisbelow(True)
    ax.set_facecolor(_SURFACE)


def write_plots(prec: list[dict], longctx: list[dict], moe: list[dict],
                out_dir: str = "plots") -> list[str]:
    """Committed PNGs — the twin of ``fp8/visualize_code.ipynb`` cells
    7-10 (matplotlib TFLOPS / tok-s charts)."""
    import matplotlib
    matplotlib.use("Agg")
    import matplotlib.pyplot as plt
    Path(out_dir).mkdir(exist_ok=True)
    written = []

    # --- precision sweep: TFLOPS/dev by seq, series = precision --------
    models = sorted({r["model"] for r in prec})
    precs = [q for q in ("bf16", "int8", "int8_bwd", "int8_pallas")
             if any(r["precision"] == q for r in prec)]
    if models and precs:
        fig, axes = plt.subplots(1, len(models),
                                 figsize=(4.6 * len(models), 3.4),
                                 facecolor=_SURFACE, squeeze=False)
        for ax, m in zip(axes[0], models):
            seqs = sorted({r["sequence_length"] for r in prec
                           if r["model"] == m})
            w = 0.8 / len(precs)
            for i, q in enumerate(precs):
                vals = []
                for s in seqs:
                    rs = [r for r in prec if r["model"] == m
                          and r["precision"] == q
                          and r["sequence_length"] == s]
                    vals.append(rs[0]["tflops_per_device"] if rs
                                else 0.0)
                xs = [j + (i - (len(precs) - 1) / 2) * w
                      for j in range(len(seqs))]
                ax.bar(xs, vals, width=w * 0.92, color=_SERIES[i],
                       label=q, zorder=2)
            ax.set_xticks(range(len(seqs)), [str(s) for s in seqs])
            ax.set_title(m, color=_INK, fontsize=10)
            ax.set_xlabel("sequence length", color=_INK2, fontsize=9)
            _style_axes(ax)
        axes[0][0].set_ylabel("TFLOPS / device", color=_INK2, fontsize=9)
        axes[0][-1].legend(frameon=False, fontsize=8, labelcolor=_INK2)
        fig.suptitle("Precision sweep — achieved TFLOPS per device",
                     color=_INK, fontsize=11)
        fig.tight_layout()
        f = f"{out_dir}/precision_tflops.png"
        fig.savefig(f, dpi=150, facecolor=_SURFACE)
        plt.close(fig)
        written.append(f)

    # --- long-context curve -------------------------------------------
    lrows = sorted((r for r in longctx if "tflops_per_device" in r),
                   key=lambda r: r["seq_len"])
    if lrows:
        fig, ax = plt.subplots(figsize=(5.4, 3.4), facecolor=_SURFACE)
        precs_l = []
        for r in lrows:   # series per precision, fixed slot order
            q = r.get("config", {}).get("matmul_precision", "bf16")
            if q not in precs_l:
                precs_l.append(q)
        allx = sorted({r["seq_len"] for r in lrows})
        for i, q in enumerate(precs_l):
            rs = [r for r in lrows
                  if r.get("config", {}).get("matmul_precision",
                                             "bf16") == q]
            xs = [r["seq_len"] for r in rs]
            ys = [r["tflops_per_device"] for r in rs]
            ax.plot(xs, ys, color=_SERIES[i], linewidth=2, marker="o",
                    markersize=5, zorder=3, label=q)
            for x, y in zip(xs, ys):
                ax.annotate(f"{y:.0f}", (x, y),
                            textcoords="offset points", xytext=(0, 7),
                            ha="center", fontsize=8, color=_INK2)
        if len(precs_l) > 1:
            ax.legend(frameon=False, fontsize=8, labelcolor=_INK2)
        xs = allx
        ax.set_xscale("log", base=2)
        ax.set_xticks(xs, [f"{x // 1024}k" for x in xs])
        ax.set_xlabel("sequence length (one chip, batch 1)",
                      color=_INK2, fontsize=9)
        ax.set_ylabel("TFLOPS / device", color=_INK2, fontsize=9)
        ax.set_title("Long-context training throughput", color=_INK,
                     fontsize=11)
        _style_axes(ax)
        fig.tight_layout()
        f = f"{out_dir}/longcontext_tflops.png"
        fig.savefig(f, dpi=150, facecolor=_SURFACE)
        plt.close(fig)
        written.append(f)

    # --- MoE: tok/s by dispatch × capacity ----------------------------
    mrows = [r for r in moe if "tflops_per_device" in r
             and r.get("batch") == 4]
    if mrows:
        fig, ax = plt.subplots(figsize=(6.4, 3.6), facecolor=_SURFACE)
        labels, vals, colors = [], [], []
        order = {"grouped": 0, "sort": 1, "einsum": 2}
        mrows.sort(key=lambda r: (order.get(
            r["config"].get("moe_dispatch", "?"), 9),
            r["config"].get("moe_top_k", 1),
            r["config"].get("moe_capacity_factor", 2.0)))
        for r in mrows:
            c = r["config"]
            disp = c.get("moe_dispatch", "?")
            k = c.get("moe_top_k", 1)
            labels.append(f"{disp}\ncf {c.get('moe_capacity_factor', 2.0)}"
                          + (f"\ntop-{k}" if k > 1 else "")
                          + ("\nint8" if "int8" in
                             c.get("matmul_precision", "") else ""))
            vals.append(r["tokens_per_sec"])
            colors.append(_SERIES[order.get(disp, 0) % len(_SERIES)])
        ax.bar(range(len(vals)), vals, width=0.62, color=colors, zorder=2)
        for i, v in enumerate(vals):
            ax.annotate(f"{v / 1e3:.1f}k", (i, v), ha="center",
                        xytext=(0, 4), textcoords="offset points",
                        fontsize=8, color=_INK2)
        ax.set_xticks(range(len(labels)), labels, fontsize=8)
        ax.set_ylabel("tokens / s", color=_INK2, fontsize=9)
        ax.set_title("MoE throughput by dispatch — 3B-L8, 8 experts, "
                     "seq 8192, b4", color=_INK, fontsize=10)
        _style_axes(ax)
        fig.tight_layout()
        f = f"{out_dir}/moe_dispatch_toks.png"
        fig.savefig(f, dpi=150, facecolor=_SURFACE)
        plt.close(fig)
        written.append(f)
    return written


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--precision-dir", default="precision_results")
    p.add_argument("--pp-dir", default="pp_results")
    p.add_argument("--longctx-dir", default="longcontext_results")
    p.add_argument("--moe-dir", default="moe_results")
    p.add_argument("--decode-dir", default="decode_results")
    p.add_argument("--out", default="RESULTS.md")
    p.add_argument("--plots", action="store_true",
                   help="additionally render PNG charts under plots/")
    args = p.parse_args(argv)

    prec, prec_fails = load_precision(args.precision_dir)
    pp = load_pp(args.pp_dir)
    longctx = load_longctx(args.longctx_dir)
    moe = _load_json_rows(args.moe_dir)
    doc = [
        "# Benchmark results",
        "",
        "**Provenance (PR 28).** Everything below was taken before "
        "this repo had a benchmark and a ledger, by a knob-matrix "
        "script and one-off scripts on shapes of their own, with no "
        "correctness check and no spread between runs; the "
        "knob-matrix script, its record files, `scripts/long_context.py` "
        "and `scripts/moe_bench.py` have left the tree, so several "
        "tables can no longer be re-taken. Tables timed on the CPU "
        "simulator are control-flow counts and say nothing of a "
        "device. Read every rate, utilisation and speed-up here as "
        "a place to look, not as this system's speed: that is in "
        "`PERF.md` and `PERF_LEDGER.jsonl`.",
        "",
        "Regenerated from committed JSON artifacts by "
        "`python scripts/analyze_results.py` — the twin of the reference's "
        "`fp8/visualize_code.ipynb` analysis pass.",
        "",
        "> Going forward, training runs emit structured telemetry",
        "> (`<results_dir>/<run_id>/{manifest.json,steps.jsonl,"
        "summary.json}`)",
        "> and future result files are generated from those run dirs via",
        "> `python scripts/report.py` (side-by-side strategy table + "
        "regression",
        "> deltas) — see \"Telemetry & run reports\" in `README.md`.  "
        "The bespoke",
        "> per-script JSON artifacts below predate that layer.",
        "",
        "## Flagship training runs (`scripts/train_flagship.py`)",
        "",
        flagship_section(),
        "## Precision sweep (model × seq × precision, batch-swept)",
        "",
        "`int8` = dynamic-absmax int8 forward matmuls; `int8_bwd` "
        "additionally quantizes both backward matmuls (the full torchao "
        "dynamic recipe at v5e's native low precision).",
        "",
        precision_tables(prec, prec_fails),
        "## Pipeline schedules (GPipe vs 1F1B)",
        "",
        pp_table(pp),
        "## Long-context single-chip sweep (`longcontext_results/`)",
        "",
        "The reference's longest trained sequence is 8192; these rows "
        "are one-chip training steps of the 3B-geometry flagship "
        "(splash attention + streamed-vocab loss + full remat).",
        "",
        longctx_table(longctx),
        "## MoE transformer (`moe_results/`)",
        "",
        "Switch-MoE flagship geometry (8 experts × 2752 ffn — the dense "
        "3B-L8 MLP split 4-ways active), FSDP train step.  Dispatch "
        "modes: grouped (per-group one-hot matmuls, r3 default) vs "
        "sort (global-capacity gather) vs whole-chunk einsum oracle; "
        "cf = capacity factor.  Dense same-model rows for comparison: "
        "the FSDP knob matrix above.  TFLOPS counts ACTIVE (top-1) "
        "FLOPs." + moe_drop_note(args.moe_dir),
        "",
        moe_table(moe),
        moe_quality_section(args.moe_dir),
        "## Autoregressive decode (`scripts/decode_bench.py`)",
        "",
        decode_table(_load_json_rows(args.decode_dir)),
        overlap_section(),
    ]
    if args.plots:
        pngs = write_plots(best_by_batch(prec), longctx, moe)
        doc += ["## Plots", ""] + [f"![{Path(f).stem}]({f})" for f in pngs]
        doc.append("")
        print(f"[analyze] plots: {', '.join(pngs)}")
    Path(args.out).write_text("\n".join(doc))
    print(f"[analyze] {len(prec)} precision rows, {len(pp)} pp rows, "
          f"{len(longctx)} long-context rows, {len(moe)} moe rows "
          f"-> {args.out}")


if __name__ == "__main__":
    main()
