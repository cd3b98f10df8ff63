"""Post-hoc comm-vs-compute split from XLA profiler traces.

Twin of the reference's in-step communication timers
(``zero/zero2.py:91-135,219-228``: cuda-synchronized stopwatches around each
``dist`` call, printed as "communication overhead %").  Under jit there is
nothing to stopwatch — collectives are ops inside one compiled program — so
the split is recovered from the profiler trace instead: sum the durations of
collective-ish ops vs compute-ish ops in the chrome-trace JSON that
``jax.profiler`` writes (``plugins/profile/<ts>/*.trace.json.gz``).

Methodology notes (honest limits):
  * Trace events are HLO instructions; names keep their primitive root
    ("psum.7", "all-reduce.3", "fusion.12"), so classification is by name
    pattern.  Collective wait time shows up as Rendezvous (CPU backend) /
    megacore-fusion-wait (TPU) and counts as comm.
  * On overlap-capable hardware comm hidden under compute still counts
    toward comm time — the split is "time attributable to", not "critical
    path", matching what the reference's blocking timers measured.
  * Infra events (thread waits, host python, dispatch) belong to neither
    bucket and are excluded from the denominator.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
from dataclasses import dataclass

_COMM = re.compile(
    r"(all[-_]?reduce|all[-_]?gather|reduce[-_]?scatter|all[-_]?to[-_]?all"
    r"|collective[-_]?permute|psum|ppermute|rendezvous(?![ -_]?callback)"
    r"|send|recv|megacore[-_]?fusion[-_]?wait)",
    re.IGNORECASE)
_COMPUTE = re.compile(
    r"(^dot|\bdot\b|fusion|convolution|cumsum|reduce|transpose|copy|scatter"
    r"|gather|broadcast_in_dim|select|compare|add|multiply|divide|subtract"
    r"|exponential|log|rsqrt|tanh|iota|concatenate|slice|dynamic|pad|while"
    r"|convert|bitcast|clamp|maximum|minimum|negate|power|remainder|sign"
    r"|custom[-_]?call|tpu[-_]?custom)",
    re.IGNORECASE)
_IGNORE = re.compile(
    r"(Wait|PjitFunction|PjRt|block_until_ready|try_to_block|shard_arg"
    r"|\$|rendezvous callback|process_name|thread_name|program_interface)",
    re.IGNORECASE)


@dataclass
class CommSplit:
    comm_us: float
    compute_us: float
    other_us: float
    trace_file: str
    top_comm: list
    top_compute: list
    # wall-clock microseconds during which a comm event and a compute
    # event were running concurrently (different trace rows) — the
    # overlap the async pump/prefetcher exist to create
    overlap_us: float = 0.0

    @property
    def total_us(self) -> float:
        return self.comm_us + self.compute_us

    @property
    def comm_fraction(self) -> float:
        return self.comm_us / self.total_us if self.total_us else 0.0

    @property
    def overlap_fraction(self) -> float:
        """Fraction of comm time hidden under concurrent compute.
        0.0 on a fully serialized schedule (e.g. the CPU-sim backend)."""
        return self.overlap_us / self.comm_us if self.comm_us else 0.0

    def report(self, label: str = "") -> str:
        """The reference's print format (zero2.py:219-228): absolute times
        + overhead %."""
        pct = 100.0 * self.comm_fraction
        return (f"[{label}] comm/compute split (profiler trace): "
                f"comm {self.comm_us / 1e3:.2f} ms, "
                f"compute {self.compute_us / 1e3:.2f} ms "
                f"-> communication overhead {pct:.1f}% of categorized "
                f"device time, {100.0 * self.overlap_fraction:.1f}% of "
                f"comm overlapped with compute")


def profile_session_dirs(trace_dir: str) -> list[str]:
    """The profiler session directories under ``trace_dir``
    (``plugins/profile/<timestamp>/`` — one per start/stop_trace pair),
    sorted by name (timestamps sort chronologically)."""
    root = os.path.join(trace_dir, "plugins", "profile")
    try:
        return sorted(os.path.join(root, d) for d in os.listdir(root)
                      if os.path.isdir(os.path.join(root, d)))
    except OSError:
        return []


def latest_trace_file(trace_dir: str, session: str | None = None) \
        -> str | None:
    """Newest ``*.trace.json.gz`` under ``trace_dir`` — or, when
    ``session`` names a profiler session directory (absolute, or relative
    to ``trace_dir``), the trace inside exactly that session.  Passing
    the owned session fixes the misattribution hazard of the bare-mtime
    form: a concurrent run or a stale ``profiler_traces/`` entry can be
    newer than the trace this run actually wrote."""
    roots = [trace_dir]
    if session:
        sd = session if os.path.isabs(session) \
            else os.path.join(trace_dir, session)
        if os.path.isdir(sd):
            roots = [sd]
    files = []
    for r in roots:
        files += glob.glob(os.path.join(r, "**", "*.trace.json.gz"),
                           recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def interval_overlap_us(comm_iv: list, compute_iv: list) -> float:
    """Total microseconds during which any ``comm`` interval and any
    ``compute`` interval (each ``(start, end)``) run concurrently.
    Compute intervals are merged first so stacked fusions don't double-
    count; each comm interval then contributes its intersection with the
    merged compute timeline."""
    if not comm_iv or not compute_iv:
        return 0.0
    merged: list[list[float]] = []
    for s, e in sorted(compute_iv):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    total = 0.0
    for cs, ce in comm_iv:
        for ms, me in merged:
            if ms >= ce:
                break
            if me <= cs:
                continue
            total += min(ce, me) - max(cs, ms)
    return total


def split_from_trace(trace_dir: str, top_n: int = 5,
                     session: str | None = None) -> CommSplit | None:
    """Analyze the trace under ``trace_dir`` — the one in the owned
    ``session`` directory when given (see :func:`latest_trace_file`),
    else the newest.  Returns None when no trace exists (profiling
    disabled / single uncaptured step)."""
    tf = latest_trace_file(trace_dir, session=session)
    if tf is None:
        return None
    events = json.load(gzip.open(tf, "rt"))["traceEvents"]
    comm: dict[str, float] = {}
    compute: dict[str, float] = {}
    comm_iv: list = []
    compute_iv: list = []
    other = 0.0
    for e in events:
        if e.get("ph") != "X":
            continue
        name = e.get("name", "")
        dur = float(e.get("dur", 0.0))
        ts = e.get("ts")
        iv = (float(ts), float(ts) + dur) if ts is not None and dur else None
        # Comm first: collective stall events ("megacore-fusion-wait",
        # "Rendezvous") must win over _IGNORE's generic host-wait patterns
        # (the docstring's methodology note depends on it).
        if _COMM.search(name):
            comm[name] = comm.get(name, 0.0) + dur
            if iv:
                comm_iv.append(iv)
        elif _IGNORE.search(name):
            continue
        elif _COMPUTE.search(name):
            compute[name] = compute.get(name, 0.0) + dur
            if iv:
                compute_iv.append(iv)
        else:
            other += dur
    top = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top_n]
    return CommSplit(
        comm_us=sum(comm.values()),
        compute_us=sum(compute.values()),
        other_us=other,
        trace_file=tf,
        top_comm=top(comm),
        top_compute=top(compute),
        overlap_us=interval_overlap_us(comm_iv, compute_iv),
    )


# -------------------------------------------- per-instance collectives
#
# Trace event names of device ops ARE compiled-HLO instruction names, one
# event per participating device row per invocation.  XLA names a
# collective instruction after the JAX primitive that produced it
# ("psum.7", "all_gather.42") and falls back to the opcode for the ones it
# creates itself ("all-reduce.1", "all-gather-start.3"), so both
# spellings are collective events.  This extracts the per-instruction
# stats the CollectiveLedger (telemetry.ledger) joins against
# ops.hlo.collective_instances.

_COLLECTIVE_EVENT_RE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all|psum|pmax|pmin|all_gather|reduce_scatter|ppermute|"
    r"all_to_all)(-start|-done)?(\.\d+)?$")


def normalize_event_name(name: str) -> str:
    """Trace event name -> HLO instruction name: strip a leading ``%``
    and any ``scope/`` prefixes XLA may attach."""
    return name.rsplit("/", 1)[-1].lstrip("%")


def collective_event_stats(trace_file: str) -> dict[str, dict]:
    """Per-instruction stats of every collective duration event in one
    chrome-trace file: ``{instruction name: {"count", "total_us"}}``.
    ``count`` sums across device rows (n_devices × invocations), so
    ``total_us/count`` is the mean duration of one device's
    participation — the number bandwidth math wants."""
    events = json.load(gzip.open(trace_file, "rt"))["traceEvents"]
    out: dict[str, dict] = {}
    for e in events:
        if e.get("ph") != "X":
            continue
        name = normalize_event_name(e.get("name", ""))
        if not _COLLECTIVE_EVENT_RE.match(name):
            continue
        rec = out.setdefault(name, {"count": 0, "total_us": 0.0})
        rec["count"] += 1
        rec["total_us"] += float(e.get("dur", 0.0))
    return out


# --------------------------------------------------- HLO schedule shape

HLO_COLLECTIVES = ("all-gather", "reduce-scatter", "all-reduce",
                   "collective-permute", "all-to-all")


def hlo_computations(txt: str) -> dict[str, list[str]]:
    """Optimized-HLO text -> {computation name: instruction lines}.
    Header args may contain nested parens (tuple types), hence the
    greedy match up to the arrow."""
    comps: dict[str, list[str]] = {}
    cur = None
    for line in txt.splitlines():
        m = re.match(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->.*\{",
                     line)
        if m:
            cur = m.group(1)
            comps[cur] = []
        elif cur is not None:
            comps[cur].append(line)
    return comps


def while_bodies(txt: str) -> set[str]:
    """Names of computations used as while-loop bodies."""
    return {m.group(1) for m in re.finditer(r"body=%?([\w\.\-]+)", txt)}


def collective_placement(txt: str) -> dict:
    """Per collective kind: how many sit inside while-loop bodies vs
    hoisted outside, plus async start/done pair count — the schedule-
    shape evidence behind ``scripts/overlap_analysis.py`` (the ZeRO-3
    in-loop re-gather vs ZeRO-2 hoisted gather distinction, reference
    ``fsdp/train_fsdp.py:84-88``)."""
    comps = hlo_computations(txt)
    bodies = while_bodies(txt)
    out: dict = {}
    for kind in HLO_COLLECTIVES:
        def count(lines):
            return sum(1 for l in lines
                       if f"{kind}(" in l or f"{kind}-start(" in l)
        in_loop = sum(count(lines) for name, lines in comps.items()
                      if name in bodies)
        total = sum(count(lines) for lines in comps.values())
        if total:
            out[kind] = {"total": total, "in_loop_body": in_loop,
                         "hoisted": total - in_loop}
    # opcode-anchored: a raw substring count would also hit the
    # instruction's own %name and the operand reference in the paired
    # -done line (~3 hits per actual pair).  Counted for EVERY
    # collective kind — async reduce-scatter/all-reduce pairs are
    # overlap evidence too.
    out["async_pairs"] = sum(txt.count(f"{kind}-start(")
                             for kind in HLO_COLLECTIVES)
    return out
