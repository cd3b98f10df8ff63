"""CollectiveLedger: per-collective bus-bandwidth attribution from traces.

BASELINE.json's metric is "collective bus-bandwidth (GB/s), NCCL vs
ICI"; the static side of that story has existed since the contract
registry (every expected collective site with approximate payload), but
nothing measured those sites on the real timeline.  This module closes
the loop:

  1. ``utils.trace_analysis.collective_event_stats`` reads the
     profiler's ``.xplane.pb`` and gives one record per collective
     *event name*: the instruction the profiler records the collective
     under, in flight from an async one's start to its done;
  2. :func:`collective_sites` reads the same names out of the compiled
     text — which instruction EXECUTES each collective, with the payload
     bytes, dtype, replica groups and mesh axis of the collective it
     stands for — and the two are joined by name;
  3. achieved algorithm- and bus-bandwidth per instruction follow from
     nccl-tests accounting (``ops.busbench.bus_factor``), aggregated by
     (op kind, pow-2 payload bucket, mesh axis);
  4. the ledger is joined against the strategy's serialized
     ``CollectiveContract`` verdict: every site must be measured (zero
     ``missing_from_trace``), nothing measured may be outside the
     program (zero ``unmatched_measured``), and the compiled site count
     of the collectives the program wrote must not exceed the contract's
     expected range.

What executes a collective on a TPU, and the rule of the join each form
needed (the four-chip FSDP step, PR 52: before them 14 of its 55
compiled collective instructions joined, the 14 synchronous ones XLA
left standing under their own names):

  * *a fusion around it* (41 of the 55): an instruction inside a fused
    computation never has an event; the FUSION that ``calls=`` its
    computation has.  The site is named after the caller.
  * *an all-reduce-scatter fusion* (4 of those): XLA:TPU runs a
    reduce-scatter as ``fusion(...), calls=%all-reduce-scatter.N``, an
    all-reduce and the slice of it in one kernel.  The site is a
    ``reduce_scatter`` whose message is the all-reduce's whole tensor.
  * *an async collective fusion* (37 of those, 12 collectives): one
    all-gather is split over ``%async-collective-start.N``, one or two
    compute fusions that carry it forward
    (``calls=%async_collective_fusion.M``) and
    ``%async-collective-done.N``, each holding a copy of the
    instruction.  ONE site, named after the start; the copies in the
    continuation and done fusions are the same message, not more bytes.
  * *a tuple-shaped instruction* (5 events the old join called "outside
    the program"): ``collective-permute-start`` and a combined
    ``all-reduce`` have tuple shapes whose TPU layouts hold parentheses;
    ``ops.hlo._INSTR_RE`` now reads them.  An async ``-start`` of an
    all-gather or a permute returns ``(operand, result, ...)``: its
    message is the result alone.
  * *a ``while`` body*: a site in a scanned layer executes once a trip;
    how often comes from the trace (``occurrences``), never from the
    text.
  * *the compiler's own collectives*: an instruction with no ``op_name``
    was written by XLA, not by the strategy (a combiner's product, a
    small reduce-scatter run as an all-reduce, the halo permutes that
    re-align a padded reduce-scatter's shards).  It is measured and
    listed like any other and is NOT counted against the contract,
    whose lowered sites it does not map onto one-to-one.

``TelemetryRun.finalize`` writes the result as ``collectives.json`` in
the run dir and lands the measured verdict in ``manifest.json`` beside
the static one; ``scripts/report.py`` renders the NCCL-vs-ICI table
from it and gates on cross-run bandwidth regressions.

Substrate honesty: on the CPU-sim mesh the GB/s numbers measure host
memory choreography — the *join* (every contract site measured, payload
accounting, regression mechanics) is what the tier-1 suite pins; real
ICI GB/s come from the same code path on a multi-chip slice.
"""

from __future__ import annotations

import json
import math
import os
import re
from dataclasses import asdict, dataclass, field

LEDGER_FILENAME = "collectives.json"
LEDGER_SCHEMA_VERSION = 1

# trace-event names ending in "-done" are the wait half of an async
# collective pair: no matching parseable instruction payload (the
# "-start" op carries it), so their time is pooled, never "unmatched"
_DONE_SUFFIXES = ("-done",)


def payload_bucket(nbytes: int) -> str:
    """Pow-2 payload bucket label ("≤4KiB", "≤1MiB", ...) — the nccl-tests
    message-size axis, coarse enough to survive shape jitter between
    runs being diffed."""
    if nbytes <= 0:
        return "0B"
    exp = max(math.ceil(math.log2(nbytes)), 0)
    size = 1 << exp
    for unit, scale in (("GiB", 30), ("MiB", 20), ("KiB", 10)):
        if size >= (1 << scale):
            return f"≤{size >> scale}{unit}"
    return f"≤{size}B"


def _axis_for_group(group_size: int, axis_sizes: dict) -> str:
    """Mesh-axis attribution of one replica-group size: the full mesh ->
    "all", exactly one axis of that size -> its name, ambiguous ->
    "a|b", no match -> "?"."""
    ws = int(math.prod(axis_sizes.values())) if axis_sizes else 1
    if group_size == ws and ws > 1:
        multi = [a for a, s in axis_sizes.items() if int(s) > 1]
        if len(multi) == 1:
            return multi[0]
        return "all"
    names = sorted(a for a, s in axis_sizes.items() if int(s) == group_size)
    if len(names) == 1:
        return names[0]
    if names:
        return "|".join(names)
    return "?"


@dataclass
class LedgerEntry:
    """One measured collective instruction: trace stats ⋈ HLO payload."""
    name: str            # HLO instruction name == trace event name
    kind: str            # "all_reduce", ... (count_collectives keys)
    occurrences: int     # trace events (device rows × invocations)
    total_us: float
    mean_us: float       # per-participation mean — the bandwidth basis
    payload_bytes: int   # nccl-tests-sized message (full logical tensor)
    dtype: str = ""
    group_size: int = 1
    axis: str = "?"
    algbw_gbps: float = 0.0
    busbw_gbps: float = 0.0
    # written by XLA, not by the strategy (no op_name): measured like any
    # other, not counted against the contract
    compiler_made: bool = False

    def to_dict(self) -> dict:
        return asdict(self)


@dataclass
class CollectiveLedger:
    entries: list[LedgerEntry] = field(default_factory=list)
    # collective-named trace events with no instruction in the program
    # (concurrent run's trace, or a parse gap) — any entry here fails
    # the contract join
    unmatched_events: dict = field(default_factory=dict)
    # program collectives that never appeared in the trace (profiler
    # window missed them, or the trace belongs to another program)
    unmeasured_instances: list = field(default_factory=list)
    async_done_us: float = 0.0
    axis_sizes: dict = field(default_factory=dict)
    contract_join: dict | None = None

    # ---- derived --------------------------------------------------------
    def sites_by_kind(self, measured_only: bool = True) -> dict[str, int]:
        """Distinct site count per kind, of the collectives the program
        wrote (the compiler's own are left out).  With
        ``measured_only=False`` the unmeasured sites are included — that
        total is what the contract range is checked against."""
        out: dict[str, int] = {}
        recs = [e.to_dict() for e in self.entries]
        if not measured_only:
            recs += list(self.unmeasured_instances)
        for rec in recs:
            if not rec.get("compiler_made"):
                out[rec["kind"]] = out.get(rec["kind"], 0) + 1
        return out

    def aggregates(self) -> dict[str, dict]:
        """(kind, payload bucket, axis) -> pooled stats.  Bandwidth is
        time-weighted over the pooled events (total bytes over total
        time), not a mean of means."""
        out: dict[str, dict] = {}
        for e in self.entries:
            key = f"{e.kind}|{payload_bucket(e.payload_bytes)}|{e.axis}"
            a = out.setdefault(key, {
                "kind": e.kind,
                "payload_bucket": payload_bucket(e.payload_bytes),
                "axis": e.axis, "sites": 0, "events": 0,
                "total_us": 0.0, "bytes_moved": 0,
                "bus_bytes_moved": 0.0})
            a["sites"] += 1
            a["events"] += e.occurrences
            a["total_us"] += e.total_us
            a["bytes_moved"] += e.payload_bytes * e.occurrences
            factor = (e.busbw_gbps / e.algbw_gbps) if e.algbw_gbps else 1.0
            a["bus_bytes_moved"] += e.payload_bytes * e.occurrences * factor
        for a in out.values():
            t = a["total_us"]
            a["algbw_gbps"] = round(a["bytes_moved"] / t / 1e3, 4) if t \
                else 0.0
            a["busbw_gbps"] = round(a["bus_bytes_moved"] / t / 1e3, 4) \
                if t else 0.0
            a["bus_bytes_moved"] = round(a["bus_bytes_moved"], 1)
        return out

    def totals(self) -> dict:
        total_us = sum(e.total_us for e in self.entries)
        bus_bytes = sum(
            e.payload_bytes * e.occurrences
            * ((e.busbw_gbps / e.algbw_gbps) if e.algbw_gbps else 1.0)
            for e in self.entries)
        return {
            "measured_sites": len(self.entries),
            "unmeasured_sites": len(self.unmeasured_instances),
            "unmatched_events": len(self.unmatched_events),
            "events": sum(e.occurrences for e in self.entries),
            "total_us": round(total_us, 3),
            "async_done_us": round(self.async_done_us, 3),
            "busbw_gbps": round(bus_bytes / total_us / 1e3, 4)
            if total_us else 0.0,
        }

    # ---- serialization --------------------------------------------------
    def to_dict(self) -> dict:
        return {
            "schema": LEDGER_SCHEMA_VERSION,
            "axis_sizes": dict(self.axis_sizes),
            "totals": self.totals(),
            "entries": [e.to_dict() for e in self.entries],
            "aggregates": self.aggregates(),
            "unmatched_events": dict(self.unmatched_events),
            "unmeasured_instances": list(self.unmeasured_instances),
            "contract_join": self.contract_join,
        }

    def write(self, run_dir: str) -> str:
        path = os.path.join(run_dir, LEDGER_FILENAME)
        with open(path, "w") as f:
            json.dump(self.to_dict(), f, indent=2, default=str)
            f.write("\n")
        return path


# ------------------------------------------------------------------ sites

@dataclass(frozen=True)
class CollectiveSite:
    """One collective of a compiled program under the name of the
    instruction that executes it (module docstring: the join's rules)."""
    name: str
    kind: str
    payload_bytes: int      # nccl-tests message: the full logical tensor
    dtype: str = ""
    group_size: int = 0     # 0: the text gives no replica groups
    path: str = ""          # the collective's own op_name
    compiler_made: bool = False


_NAME_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")


def collective_sites(hlo_text: str) -> list[CollectiveSite]:
    """Every collective of compiled HLO text, once, under the name its
    trace events carry.  Message sizing follows nccl-tests so the GB/s are
    column-comparable with the reference's NCCL numbers: the full logical
    tensor — an instruction's *output* bytes for all_reduce / all_gather /
    all_to_all / collective_permute, and output × group_size for a
    reduce_scatter (whose output is the already-scattered shard)."""
    from ..ops.hlo import _DTYPE_BYTES, _OP_NAME_RE, collective_instances
    from ..utils.trace_analysis import (ASYNC_FUSION_RE, CALLS_RE,
                                        hlo_computations)

    where: dict[str, str] = {}       # instruction -> its computation
    caller: dict[str, str] = {}      # fused computation -> the fusion
    for comp, lines in hlo_computations(hlo_text).items():
        for line in lines:
            m = _NAME_RE.match(line)
            if not m:
                continue
            where[m.group(1)] = comp
            for callee in CALLS_RE.findall(line):
                caller[callee] = m.group(1)

    sites = []
    for inst in collective_instances(hlo_text):
        if not inst.name:
            continue
        name, kind, nbytes = inst.name, inst.kind, inst.bytes
        group = len(inst.replica_groups[0]) if inst.replica_groups else 0
        if kind == "reduce_scatter":
            nbytes *= max(group, 1)
        elif inst.is_async_start and len(inst.shapes) > 1 \
                and kind in ("all_gather", "collective_permute"):
            # (operand, result, context...): the message is the result
            nbytes = math.prod(inst.shapes[1]) \
                * _DTYPE_BYTES.get(inst.dtypes[1], 4)
        comp = where.get(inst.name, "")
        executes = caller.get(comp)
        if executes is not None:        # a fusion around it has the event
            fused = ASYNC_FUSION_RE.match(executes)
            if comp.startswith("async_collective_fusion") \
                    or (fused and fused.group(1) == "done"):
                continue     # a copy of a collective its start already is
            name = executes
            if comp.startswith("all-reduce-scatter"):
                kind = "reduce_scatter"   # the all-reduce's whole tensor
        op_name = _OP_NAME_RE.search(inst.line)
        sites.append(CollectiveSite(
            name=name, kind=kind, payload_bytes=int(nbytes),
            dtype=inst.dtypes[0] if inst.dtypes else "", group_size=group,
            path=op_name.group(1) if op_name else "",
            compiler_made=op_name is None))
    return sites


# ------------------------------------------------------------------ build

def build_ledger(event_stats: dict, hlo_text: str,
                 axis_sizes: dict | None = None) -> CollectiveLedger:
    """Join per-event-name trace stats (``collective_event_stats``)
    against the compiled program's collective sites
    (:func:`collective_sites`, whose payloads are nccl-tests messages)."""
    from ..ops.busbench import bus_factor

    axis_sizes = {k: int(v) for k, v in (axis_sizes or {}).items()}
    ws = int(math.prod(axis_sizes.values())) if axis_sizes else 1
    sites = {s.name: s for s in collective_sites(hlo_text)}

    led = CollectiveLedger(axis_sizes=axis_sizes)
    matched = set()
    for name, stats in sorted(event_stats.items()):
        site = sites.get(name)
        if site is None:
            if name.split(".")[0].endswith(_DONE_SUFFIXES):
                led.async_done_us += float(stats["total_us"])
            else:
                led.unmatched_events[name] = dict(stats)
            continue
        matched.add(name)
        count = int(stats["count"])
        total_us = float(stats["total_us"])
        mean_us = total_us / count if count else 0.0
        group = site.group_size or max(ws, 1)
        algbw = site.payload_bytes / mean_us / 1e3 if mean_us else 0.0
        led.entries.append(LedgerEntry(
            name=name, kind=site.kind, occurrences=count,
            total_us=round(total_us, 3), mean_us=round(mean_us, 4),
            payload_bytes=site.payload_bytes, dtype=site.dtype,
            group_size=group,
            axis=_axis_for_group(group, axis_sizes),
            algbw_gbps=round(algbw, 4),
            busbw_gbps=round(algbw * bus_factor(site.kind, group), 4),
            compiler_made=site.compiler_made))
    led.unmeasured_instances = [
        {"name": n, "kind": s.kind, "payload_bytes": s.payload_bytes,
         "compiler_made": s.compiler_made}
        for n, s in sorted(sites.items()) if n not in matched]
    return led


def ledger_from_trace(trace_dir: str, hlo_text: str,
                      axis_sizes: dict | None = None,
                      session: str | None = None) -> CollectiveLedger | None:
    """Convenience: locate the (owned) trace file under ``trace_dir``
    and build the ledger.  None when no trace exists."""
    from ..utils.trace_analysis import (collective_event_stats,
                                        latest_xplane_file)
    tf = latest_xplane_file(trace_dir, session=session)
    if tf is None:
        return None
    return build_ledger(collective_event_stats(tf), hlo_text, axis_sizes)


# ------------------------------------------------------------ contract join

def join_contract(ledger: CollectiveLedger, expected: dict,
                  strategy: str = "") -> dict:
    """Measured-side contract verdict: the trace-joined twin of
    ``analysis.check_counts``.  ``expected`` is the serialized verdict's
    expected dict (int / ``"lo..hi"`` / ``"any"`` per kind).  ok iff

      * every program collective was measured (no ``missing_from_trace``),
      * no collective-named trace event fell outside the program
        (no ``unmatched_measured``), and
      * the compiled site count per kind, of the collectives the program
        wrote, is no higher than the expected range allows, and nonzero
        where the range is.  The range itself counts lowered (StableHLO)
        sites; XLA's collective combiners merge same-kind sites
        afterwards and XLA:TPU rewrites some into collectives of its own
        (``compiler_made``, counted apart), so a compiled program may
        hold as few as one site for many lowered ones — and none at all
        on a mesh of one device.

    The verdict is stored back on the ledger (``contract_join``) and
    returned."""
    from ..analysis.contracts import KINDS, parse_expected_spec

    compiled_sites = ledger.sites_by_kind(measured_only=False)
    measured_sites = ledger.sites_by_kind(measured_only=True)
    violations = []
    exp_out = {}
    for kind in KINDS:
        lo, hi = parse_expected_spec(expected.get(kind, 0))
        exp_out[kind] = expected.get(kind, 0)
        got = compiled_sites.get(kind, 0)
        # on a mesh of one device every collective is degenerate and XLA
        # compiles it away
        lo = min(lo, 1) if math.prod(ledger.axis_sizes.values()) > 1 else 0
        if not lo <= got <= hi:
            hi_s = "inf" if hi == math.inf else int(hi)
            violations.append(
                f"{kind}: {got} compiled sites, contract allows "
                f"{lo}..{hi_s}")
    missing = [r["name"] for r in ledger.unmeasured_instances]
    unmatched = sorted(ledger.unmatched_events)
    for n in missing:
        violations.append(f"expected site never measured in trace: {n}")
    for n in unmatched:
        violations.append(f"measured collective outside the program: {n}")
    verdict = {
        "strategy": strategy,
        "ok": not violations,
        "expected": exp_out,
        "compiled_sites": compiled_sites,
        "measured_sites": measured_sites,
        "compiler_made_sites": sum(
            1 for r in [e.to_dict() for e in ledger.entries]
            + list(ledger.unmeasured_instances) if r.get("compiler_made")),
        "missing_from_trace": missing,
        "unmatched_measured": unmatched,
        "violations": violations,
    }
    ledger.contract_join = verdict
    return verdict


# ------------------------------------------------------------- read back

def load_ledger_dict(run_dir: str) -> dict | None:
    """The raw ``collectives.json`` of one run dir, or None."""
    path = os.path.join(run_dir, LEDGER_FILENAME)
    if not os.path.isfile(path):
        return None
    try:
        return json.load(open(path))
    except (OSError, json.JSONDecodeError):
        return None


def check_bandwidth_regressions(cur_aggs: dict, base_aggs: dict,
                                max_drop_pct: float = 20.0,
                                label: str = "", base_label: str = "") \
        -> list[dict]:
    """Diff two ledgers' (kind, bucket, axis) aggregates: one record per
    key present in both, ``regressed`` when busbw dropped more than
    ``max_drop_pct`` percent — the ``--fail-on-bandwidth-regression``
    gate behind ``scripts/report.py``."""
    results = []
    for key, cur in sorted((cur_aggs or {}).items()):
        base = (base_aggs or {}).get(key)
        if not base:
            continue
        a, b = cur.get("busbw_gbps"), base.get("busbw_gbps")
        if not a or not b:
            continue
        delta_pct = (a / b - 1.0) * 100.0
        results.append({
            "run_id": label, "baseline": base_label, "key": key,
            "busbw_gbps": a, "baseline_busbw_gbps": b,
            "delta_pct": round(delta_pct, 2),
            "max_drop_pct": max_drop_pct,
            "regressed": delta_pct < -max_drop_pct,
        })
    return results
