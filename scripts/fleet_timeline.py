"""Cross-rank fleet timeline: merge N per-rank telemetry run dirs from
one ``dts-launch`` group into a single Perfetto/chrome-trace document,
plus the two reports single-run tooling cannot produce:

  * **straggler report** — for every pump sync site (same span name +
    step across >= 2 ranks), which rank arrived last and by how much,
    aggregated into per-rank "time blocked waiting on peers": the
    cross-rank twin of the single-run host_sync breakdown.  A rank that
    computes slowly arrives *late* at the barrier and barely waits; its
    peers arrive early and eat the lag — so blame lands on the last
    arrival, not the longest wait.
  * **request swimlanes** — serving spans carrying a ``trace_id`` are
    grouped per request onto their own named tracks, and the
    ``t_submit/t_admit/t_first`` stamped where a prefill ends yield a TTFT
    decomposition (queue wait + prefill) per request, counting a
    failover replay ONCE (the last completed attempt wins) while still
    listing every replica the trace touched.

Cross-rank time alignment rides the ``clock_anchor.json`` sidecar each
SpanStream writes: span timestamps are already unix-epoch µs anchored
by a bounded-error midpoint capture, so ranks merge by timestamp
directly and the report carries the worst anchor error as its
confidence bound.

  python scripts/fleet_timeline.py RUN_DIR [RUN_DIR ...]
  python scripts/fleet_timeline.py --results-dir runs --group NAME
  python scripts/fleet_timeline.py --results-dir runs   # newest group
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

# pid blocks in the merged doc: one fake "process" per rank, plus one
# for the per-request swimlanes
RANK_PID_BASE = 1000
REQUEST_PID = 2000


def _load_json(path: Path) -> dict | None:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return None


# ---------------------------------------------------------------- discovery

def discover_groups(results_dir: str) -> dict[str, list[str]]:
    """Map ``launch_group`` -> sorted run dirs under ``results_dir``.
    Runs without a stamped group fall back to a group per run_id prefix
    (strategy + timestamp with any ``-rN`` suffix stripped), so N ranks
    of one pre-group launch still merge."""
    groups: dict[str, list[str]] = {}
    root = Path(results_dir)
    if not root.is_dir():
        return groups
    for entry in sorted(root.iterdir()):
        man = _load_json(entry / "manifest.json")
        if man is None:
            continue
        group = (man.get("extra") or {}).get("launch_group")
        if not group:
            rid = man.get("run_id") or entry.name
            base = rid
            parts = rid.rsplit("-r", 1)
            if len(parts) == 2 and parts[1].isdigit():
                base = parts[0]
            group = base
        groups.setdefault(str(group), []).append(str(entry))
    return groups


def load_rank_stream(run_dir: str) -> dict:
    """One rank's merged-timeline inputs: manifest, spans, clock anchor,
    and the resolved rank (manifest extra wins, then the anchor sidecar,
    then per-span stamps, then 0)."""
    from distributed_training_sandbox_tpu.telemetry import (
        read_clock_anchor, read_spans)
    man = _load_json(Path(run_dir) / "manifest.json") or {}
    spans = read_spans(run_dir)
    anchor = read_clock_anchor(run_dir)
    rank = (man.get("extra") or {}).get("rank")
    if rank is None and anchor is not None:
        rank = anchor.get("rank")
    if rank is None and spans:
        rank = spans[0].get("rank")
    return {
        "run_dir": str(run_dir),
        "rank": int(rank or 0),
        "pid": man.get("pid") or (anchor or {}).get("pid"),
        "manifest": man,
        "spans": spans,
        "anchor": anchor,
    }


# ---------------------------------------------------------------- straggler

def straggler_report(streams: list[dict]) -> dict:
    """Per-sync-site arrival attribution across ranks.

    A sync site is a (span name, step) pair observed on >= 2 ranks with
    a ``pump`` category; arrival = span start (``ts_us``).  Per site the
    last-arriving rank is the straggler and every earlier rank's
    ``blocked_on_peers`` grows by its head start; per-rank aggregates
    and the overall straggler (largest attributed lateness) follow."""
    ranks = sorted({s["rank"] for s in streams})
    sites: dict[tuple, dict[int, dict]] = {}
    for st in streams:
        for sp in st["spans"]:
            if sp.get("cat") != "pump" or "step" not in sp:
                continue
            key = (sp["name"], int(sp["step"]))
            # one arrival per rank per site: keep the EARLIEST (retries
            # of the same site would skew attribution late)
            cur = sites.setdefault(key, {}).get(st["rank"])
            if cur is None or sp["ts_us"] < cur["ts_us"]:
                sites[key][st["rank"]] = sp
    per_rank = {r: {"blocked_on_peers_ms": 0.0, "times_last": 0,
                    "lateness_ms": 0.0, "sites": 0} for r in ranks}
    rows = []
    for (name, step), by_rank in sorted(sites.items(),
                                        key=lambda kv: (kv[0][1], kv[0][0])):
        if len(by_rank) < 2:
            continue
        arrivals = {r: sp["ts_us"] for r, sp in by_rank.items()}
        last_rank = max(arrivals, key=lambda r: arrivals[r])
        t_last = arrivals[last_rank]
        lag_ms = (t_last - min(arrivals.values())) / 1e3
        for r, t in arrivals.items():
            per_rank[r]["sites"] += 1
            per_rank[r]["blocked_on_peers_ms"] += (t_last - t) / 1e3
        per_rank[last_rank]["times_last"] += 1
        per_rank[last_rank]["lateness_ms"] += lag_ms
        rows.append({
            "name": name, "step": step, "last_rank": last_rank,
            "lag_ms": round(lag_ms, 3),
            "arrival_offset_ms": {
                str(r): round((t - min(arrivals.values())) / 1e3, 3)
                for r, t in sorted(arrivals.items())},
        })
    for agg in per_rank.values():
        agg["blocked_on_peers_ms"] = round(agg["blocked_on_peers_ms"], 3)
        agg["lateness_ms"] = round(agg["lateness_ms"], 3)
    straggler = None
    if rows:
        straggler = max(per_rank,
                        key=lambda r: (per_rank[r]["lateness_ms"],
                                       per_rank[r]["times_last"]))
    anchor_errs = [st["anchor"]["anchor_error_us"] for st in streams
                   if st.get("anchor")
                   and st["anchor"].get("anchor_error_us") is not None]
    return {
        "ranks": ranks,
        "sync_sites": rows,
        "per_rank": {str(r): agg for r, agg in per_rank.items()},
        "straggler": straggler,
        "max_anchor_error_us": (round(max(anchor_errs), 3)
                                if anchor_errs else None),
    }


# ---------------------------------------------------------------- requests

def request_report(streams: list[dict]) -> list[dict]:
    """Per-request TTFT decomposition from the spans that stamp a
    request's first token: the engine's ``serve/bookkeep`` span of a
    finished prefill carries ``trace_id`` and ``t_first_s``.  A failover
    replay leaves such spans on >= 2 replicas under ONE trace_id; only
    the LAST attempt (the one that reached first-token) is decomposed —
    the replay counts once — but every replica the trace touched is
    listed, as is the attempt count."""
    by_tid: dict[str, list[dict]] = {}
    for st in streams:
        for sp in st["spans"]:
            if sp.get("t_first_s") is None \
                    or sp.get("trace_id") is None:
                continue
            by_tid.setdefault(str(sp["trace_id"]), []).append(sp)
    out = []
    for tid, attempts in sorted(by_tid.items()):
        last = max(attempts, key=lambda s: s["ts_us"])
        replicas = sorted({s.get("replica") for s in attempts
                           if s.get("replica") is not None})
        row = {
            "trace_id": tid,
            "request_id": last.get("request_id", last.get("rid")),
            "replicas": replicas,
            "attempts": len(attempts),
            "replayed": len(attempts) > 1,
        }
        t_sub, t_adm, t_first = (last.get("t_submit_s"),
                                 last.get("t_admit_s"),
                                 last.get("t_first_s"))
        if None not in (t_sub, t_adm, t_first):
            row["queue_wait_ms"] = round(1e3 * (t_adm - t_sub), 3)
            row["prefill_ms"] = round(1e3 * (t_first - t_adm), 3)
            row["ttft_ms"] = round(1e3 * (t_first - t_sub), 3)
        out.append(row)
    return out


# ---------------------------------------------------------------- timeline

def merge_timeline(run_dirs: list[str], group: str | None = None) -> dict:
    """One Perfetto doc from N per-rank run dirs: a named process track
    per rank (threads = span categories), a ``requests`` process whose
    threads are per-trace_id swimlanes, and the straggler + request
    reports embedded under ``metadata``."""
    streams = [load_rank_stream(d) for d in run_dirs]
    streams.sort(key=lambda s: s["rank"])
    events: list[dict] = []
    all_ts = [sp["ts_us"] for st in streams for sp in st["spans"]]
    t0 = min(all_ts) if all_ts else 0.0

    tid_of_cat: dict[tuple, int] = {}
    for st in streams:
        pid = RANK_PID_BASE + st["rank"]
        label = f"rank {st['rank']}"
        if st.get("pid"):
            label += f" (pid {st['pid']})"
        events.append({"ph": "M", "name": "process_name", "pid": pid,
                       "args": {"name": label}})
        cats = sorted({sp.get("cat") or "host" for sp in st["spans"]})
        for i, cat in enumerate(cats, start=1):
            tid_of_cat[(pid, cat)] = i
            events.append({"ph": "M", "name": "thread_name", "pid": pid,
                           "tid": i, "args": {"name": cat}})
        for sp in st["spans"]:
            cat = sp.get("cat") or "host"
            args = {k: v for k, v in sp.items()
                    if k not in ("schema", "name", "cat", "ts_us",
                                 "dur_us")}
            events.append({
                "ph": "X", "name": sp["name"], "cat": cat,
                "pid": pid, "tid": tid_of_cat[(pid, cat)],
                "ts": sp["ts_us"] - t0, "dur": sp["dur_us"],
                "args": args})

    # request swimlanes: one thread per trace_id, spans from EVERY
    # replica/rank interleave on it — a replayed request reads as one
    # lane with a visible gap at the failover
    traced = [(st, sp) for st in streams for sp in st["spans"]
              if sp.get("trace_id") is not None]
    if traced:
        events.append({"ph": "M", "name": "process_name",
                       "pid": REQUEST_PID, "args": {"name": "requests"}})
        tids = sorted({str(sp["trace_id"]) for _, sp in traced})
        tid_of_trace = {t: i for i, t in enumerate(tids, start=1)}
        for t, i in tid_of_trace.items():
            events.append({"ph": "M", "name": "thread_name",
                           "pid": REQUEST_PID, "tid": i,
                           "args": {"name": t}})
        for st, sp in traced:
            args = {k: v for k, v in sp.items()
                    if k not in ("schema", "name", "cat", "ts_us",
                                 "dur_us")}
            args["rank"] = st["rank"]
            events.append({
                "ph": "X", "name": sp["name"], "cat": "request",
                "pid": REQUEST_PID,
                "tid": tid_of_trace[str(sp["trace_id"])],
                "ts": sp["ts_us"] - t0, "dur": sp["dur_us"],
                "args": args})

    # metadata first, then X events by ts — what trace viewers expect
    events.sort(key=lambda e: (0 if e["ph"] == "M" else 1,
                               e.get("ts", 0.0)))
    report = straggler_report(streams)
    requests = request_report(streams)
    return {
        "displayTimeUnit": "ms",
        "traceEvents": events,
        "metadata": {
            "group": group,
            "run_dirs": [st["run_dir"] for st in streams],
            "ranks": report["ranks"],
            "straggler_report": report,
            "requests": requests,
        },
    }


def _print_report(report: dict, requests: list[dict]) -> None:
    rows = report["sync_sites"]
    print(f"[fleet-timeline] ranks: {report['ranks']}, "
          f"{len(rows)} shared sync site(s), clock anchor error "
          f"<= {report['max_anchor_error_us']} us")
    for row in rows[:20]:
        print(f"[fleet-timeline]   {row['name']} step {row['step']}: "
              f"rank {row['last_rank']} last by {row['lag_ms']} ms")
    if len(rows) > 20:
        print(f"[fleet-timeline]   ... {len(rows) - 20} more site(s)")
    for r, agg in sorted(report["per_rank"].items()):
        print(f"[fleet-timeline] rank {r}: blocked on peers "
              f"{agg['blocked_on_peers_ms']} ms over {agg['sites']} "
              f"site(s); last {agg['times_last']}x "
              f"(+{agg['lateness_ms']} ms attributed)")
    if report["straggler"] is not None:
        print(f"[fleet-timeline] straggler: rank {report['straggler']}")
    replayed = [q for q in requests if q["replayed"]]
    if requests:
        print(f"[fleet-timeline] {len(requests)} request swimlane(s), "
              f"{len(replayed)} replayed across replicas")
    for q in replayed:
        print(f"[fleet-timeline]   {q['trace_id']}: replicas "
              f"{q['replicas']}, ttft {q.get('ttft_ms')} ms = queue "
              f"{q.get('queue_wait_ms')} + prefill {q.get('prefill_ms')}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="merge per-rank telemetry run dirs into one "
                    "Perfetto timeline + straggler report")
    p.add_argument("run_dirs", nargs="*",
                   help="per-rank telemetry run dirs to merge")
    p.add_argument("--results-dir", type=str, default=None,
                   help="discover run dirs here, grouped by the "
                        "launcher-stamped launch_group")
    p.add_argument("--group", type=str, default=None,
                   help="which launch group to merge (default: the "
                        "newest one)")
    p.add_argument("--out", type=str, default=None,
                   help="merged timeline path (default "
                        "<first run dir>/fleet_timeline.json)")
    p.add_argument("--report", type=str, default=None,
                   help="also write the straggler/request report JSON "
                        "here ('-' = stdout)")
    args = p.parse_args(argv)

    run_dirs = list(args.run_dirs)
    group = args.group
    if args.results_dir:
        groups = discover_groups(args.results_dir)
        if not groups:
            print(f"[fleet-timeline] no telemetry runs under "
                  f"{args.results_dir}", file=sys.stderr)
            return 2
        if group is None:
            # newest group by run-dir mtime
            group = max(groups, key=lambda g: max(
                os.path.getmtime(d) for d in groups[g]))
        if group not in groups:
            print(f"[fleet-timeline] group {group!r} not found; have "
                  f"{sorted(groups)}", file=sys.stderr)
            return 2
        run_dirs += groups[group]
    if not run_dirs:
        p.error("give RUN_DIR arguments or --results-dir")

    doc = merge_timeline(run_dirs, group=group)
    out = Path(args.out) if args.out \
        else Path(run_dirs[0]) / "fleet_timeline.json"
    out.parent.mkdir(parents=True, exist_ok=True)
    with open(out, "w") as f:
        json.dump(doc, f)
    n_x = sum(e["ph"] == "X" for e in doc["traceEvents"])
    print(f"[fleet-timeline] merged {len(run_dirs)} rank dir(s), "
          f"{n_x} span(s) -> {out}")
    report = doc["metadata"]["straggler_report"]
    requests = doc["metadata"]["requests"]
    _print_report(report, requests)
    if args.report:
        payload = json.dumps({"straggler_report": report,
                              "requests": requests}, indent=2)
        if args.report == "-":
            print(payload)
        else:
            Path(args.report).write_text(payload + "\n")
            print(f"[fleet-timeline] report -> {args.report}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
