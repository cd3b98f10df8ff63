"""Merge a run's host-phase spans with its device trace into one
chrome-trace timeline.

A :class:`telemetry.TelemetryRun` leaves two time-domain artifacts in
its run dir: ``spans.jsonl`` (host waits — prefetch queue, pump sync
barriers, checkpoint saves, serving bursts) and, when profiling was on,
the XLA profiler session it *owns* (``manifest.json:profile_sessions``).
This script joins them into a single ``traceEvents`` JSON that
``chrome://tracing`` / Perfetto loads directly: device rows keep the
pid/tid layout XLA wrote; host spans land on a synthetic "host phases"
process with one thread per category (pump / prefetch / checkpoint /
serve).

Clock honesty: the two sides run on DIFFERENT clocks — spans are
unix-epoch µs from a ``perf_counter``-anchored stream, device events use
XLA's internal trace timebase.  There is no cross-clock sync point to
align them exactly, so each side is zeroed to its own earliest
timestamp.  Relative durations and within-side ordering are exact;
host-vs-device alignment is approximate (both start near the profiled
window), good for "where does the host stall" reading, not for
nanosecond attribution across the boundary.

Usage:
  python scripts/export_timeline.py <run-dir> [--out timeline.json.gz]
"""

from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import sys
from pathlib import Path

# Prepend the checkout root so the source tree always wins over any
# installed copy of the package (`pip install -e .` makes this a no-op).
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

HOST_PID = 999000   # far above any XLA device pid


def _load_json(path: str) -> dict | None:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return None


def find_trace_file(run_dir: str) -> str | None:
    """The device trace this run owns, as the chrome-trace the profiler
    writes beside its ``.xplane.pb`` (jax 0.9 does, on a TPU and on the
    CPU simulator alike): the session recorded in its manifest when
    present, else newest under the summary's trace dir."""
    manifest = _load_json(os.path.join(run_dir, "manifest.json")) or {}
    summary = _load_json(os.path.join(run_dir, "summary.json")) or {}
    sessions = manifest.get("profile_sessions") or \
        summary.get("profile_sessions") or []
    trace_dir = summary.get("trace_dir")
    for root in [*reversed(sessions), trace_dir]:
        if not root or not os.path.isdir(root):
            continue
        files = glob.glob(os.path.join(root, "**", "*.trace.json.gz"),
                          recursive=True)
        if files:
            return max(files, key=os.path.getmtime)
    return None


def load_device_events(trace_file: str) -> list[dict]:
    with gzip.open(trace_file, "rt") as f:
        doc = json.load(f)
    return list(doc.get("traceEvents") or [])


def span_events(spans: list[dict]) -> list[dict]:
    """Host spans as chrome-trace ph="X" events on the synthetic host
    process, one tid per category so Perfetto gives each its own row."""
    cats = sorted({s.get("cat") or "host" for s in spans})
    tid_of = {c: i + 1 for i, c in enumerate(cats)}
    out = [{"ph": "M", "pid": HOST_PID, "name": "process_name",
            "args": {"name": "host phases"}}]
    for c in cats:
        out.append({"ph": "M", "pid": HOST_PID, "tid": tid_of[c],
                    "name": "thread_name", "args": {"name": c}})
    for s in spans:
        ev = {"ph": "X", "pid": HOST_PID,
              "tid": tid_of[s.get("cat") or "host"],
              "name": s.get("name", "?"),
              "ts": float(s.get("ts_us", 0.0)),
              "dur": float(s.get("dur_us", 0.0))}
        attrs = {k: v for k, v in s.items()
                 if k not in ("schema", "name", "cat", "ts_us", "dur_us")}
        if attrs:
            ev["args"] = attrs
        out.append(ev)
    return out


def _rebase(events: list[dict]) -> None:
    """Zero a side's ``ts`` to its own earliest event (in place)."""
    ts = [e["ts"] for e in events if "ts" in e]
    if not ts:
        return
    t0 = min(ts)
    for e in events:
        if "ts" in e:
            e["ts"] = e["ts"] - t0


def build_timeline(run_dir: str) -> dict:
    """The merged chrome-trace document for one run dir."""
    from distributed_training_sandbox_tpu.telemetry.spans import read_spans
    spans = read_spans(run_dir)
    host = span_events(spans) if spans else []
    _rebase(host)
    device: list[dict] = []
    trace_file = find_trace_file(run_dir)
    if trace_file:
        device = load_device_events(trace_file)
        _rebase(device)
    # metadata (track-naming ph="M") events first, then everything in
    # timestamp order — some viewers resolve track names lazily and
    # mis-group out-of-order streams
    merged = sorted(device + host,
                    key=lambda e: (0 if e.get("ph") == "M" else 1,
                                   e.get("ts", 0.0)))
    return {
        "displayTimeUnit": "ms",
        "traceEvents": merged,
        "metadata": {
            "run_dir": os.path.abspath(run_dir),
            "host_spans": len(spans),
            "device_trace": trace_file,
            "clock_note": ("host and device sides are independently "
                           "zeroed to their own first event; cross-side "
                           "alignment is approximate"),
        },
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("run_dir", help="telemetry run directory "
                   "(contains manifest.json / spans.jsonl)")
    p.add_argument("--out", default=None,
                   help="output path (.json or .json.gz); default "
                   "<run-dir>/timeline.json.gz")
    args = p.parse_args(argv)
    if not os.path.isdir(args.run_dir):
        print(f"error: not a directory: {args.run_dir}", file=sys.stderr)
        return 2
    doc = build_timeline(args.run_dir)
    if not doc["traceEvents"]:
        print(f"error: {args.run_dir} has neither spans.jsonl nor an "
              f"owned device trace — nothing to export", file=sys.stderr)
        return 1
    out = args.out or os.path.join(args.run_dir, "timeline.json.gz")
    if out.endswith(".gz"):
        with gzip.open(out, "wt") as f:
            json.dump(doc, f)
    else:
        with open(out, "w") as f:
            json.dump(doc, f, indent=1)
    n_dev = sum(1 for e in doc["traceEvents"]
                if e.get("pid") != HOST_PID and e.get("ph") == "X")
    n_host = doc["metadata"]["host_spans"]
    print(f"wrote {out}: {n_host} host spans + {n_dev} device events "
          f"(load in chrome://tracing or ui.perfetto.dev)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
