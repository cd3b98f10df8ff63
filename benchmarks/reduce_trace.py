"""The one reducer: from a profiler trace to what the per-layer metrics read.

Two stages, so that the arithmetic can be checked on a small recorded trace
without a chip:

  ``load_xplane(path)``   the ``.xplane.pb`` JAX's profiler wrote -> a
                          ``RawTrace``: per device plane the events of its
                          op line and its module line, and the host spans
                          the runner put on the same clock
                          (``jax.profiler.TraceAnnotation`` names that
                          start with ``bench/``).  A ``RawTrace`` round-trips
                          through JSON (``to_json`` / ``from_json``), which
                          is what ``fixtures/`` holds.
  ``reduce(raw)``         -> a ``ReducedTrace``: per chip the busy union and
                          idle share, time per op group (numeric suffix
                          stripped) and per XLA module, collective in-flight
                          and exposed time, and the longest idle gaps, each
                          attributed to the host span open at the time.

How a v5e trace looks (jax 0.9, looked at by hand, PR 22): one plane per
chip named ``/device:TPU:<n>``; its line ``XLA Ops`` holds one event per
executed HLO instruction, and the event's name is the instruction's whole
text (``fusion.12 = bf16[4,8192,2048]{...} fusion(...), kind=kOutput,
calls=%fused_computation.3``), tuple shapes with their ``/*index=5*/``
comments included; a ``while`` is one event that spans its body's events; a
Pallas kernel is a ``custom-call`` named after its kernel function
(``splash_mha_dkv_no_residuals.11``); XLA:TPU runs a reduce-scatter as a
``fusion`` that ``calls=%all-reduce-scatter.N``.  ``instruction_name`` cuts
that text to a short name at load.  The line ``XLA Modules`` holds one event
per program launch (``jit_step(123456)``), and the line ``Async XLA Ops`` one event per
asynchronous operation (``copy-start``, a collective's ``-start``) for as
long as it is in flight.  Host threads are lines of ``/host:CPU``.

    python benchmarks/reduce_trace.py <trace.xplane.pb> [--dump|--json out]
"""

from __future__ import annotations

import bisect
import json
import re
import sys
from dataclasses import dataclass, field

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ASYNC_LINE = "Async XLA Ops"
HOST_PREFIX = "bench/"
WINDOW_SPAN = "bench/window"

#: instructions that only contain others: their time is their body's
CONTAINERS = re.compile(r"^(while|conditional|call)(\.\d+)?$")
COLLECTIVE = re.compile(
    r"^(all-gather|reduce-scatter|all-reduce|collective-permute|all-to-all"
    r"|all_gather|reduce_scatter|psum|psum_scatter|ppermute|all_to_all"
    r"|collective_permute)(-start|-done)?([._]\d+)*$")
_SUFFIX = re.compile(r"([._]\d+)+$")


def op_group(name: str) -> str:
    """``fusion.123`` -> ``fusion``; ``all-gather-start.4.1`` ->
    ``all-gather-start``: one group per kind of instruction."""
    return _SUFFIX.sub("", name.rsplit("/", 1)[-1].lstrip("%")) or name


_OPCODE = re.compile(
    r"[\s)}](all-gather|all-reduce|reduce-scatter|collective-permute"
    r"|all-to-all)(-start|-done)?\(")
_CALLEE = re.compile(
    r"calls=%?(all-reduce-scatter|reduce-scatter|all-gather|all-reduce"
    r"|collective-permute|all-to-all)")


def instruction_name(text: str) -> str:
    """A trace event's name, which on a TPU is the whole instruction text,
    cut to the instruction's name.  An instruction that IS a collective
    under another name (a fusion that calls ``%all-reduce-scatter.2``, a
    collective XLA named after the JAX primitive's output) is renamed
    after the collective, keeping its number, so that the collective
    arithmetic finds it."""
    name, sep, rest = text.partition(" = ")
    name = name.strip().lstrip("%")
    if not sep or COLLECTIVE.match(name):
        return name
    num = "".join(re.findall(r"\.\d+$", name))
    m = _CALLEE.search(rest)
    if m:
        kind = m.group(1).replace("all-reduce-scatter", "reduce-scatter")
        return f"{kind}{num}"
    m = _OPCODE.search(rest)
    if m:
        return f"{m.group(1)}{m.group(2) or ''}{num}"
    return name


def module_group(name: str) -> str:
    """``jit_step(8123456)`` -> ``jit_step``.  A program without a name of
    its own (``jit__unknown``: a jitted ``functools.partial``, as both of
    the serving engine's programs are) keeps its fingerprint, or two
    programs would fall into one group; ``alias_modules`` names them."""
    base = re.sub(r"\(\d+\)$", "", name)
    return name if base.endswith("unknown") else base


def alias_modules(red: "ReducedTrace", launches: dict[str, int],
                  slack: float = 0.1) -> dict[str, str]:
    """Tell a trace's programs apart by how often each was launched:
    ``launches`` maps a label to the number of launches the runner counted
    inside the window (``{"decode": 84, "prefill": 31}``); each label goes
    to the module whose launch count is nearest, if it is within ``slack``
    (and 2) of it and no other label's.  Returns module -> label."""
    counts = {m: n for m, (n, _) in red.chips[0].modules.items()
              if module_group(m) == m and "(" in m}     # the unnamed only
    out: dict[str, str] = {}
    for label, want in sorted(launches.items(), key=lambda kv: -kv[1]):
        free = [m for m in counts if m not in out]
        if not free or not want:
            continue
        best = min(free, key=lambda m: abs(counts[m] - want))
        if abs(counts[best] - want) <= max(2, slack * want):
            out[best] = label
    return out


@dataclass
class RawTrace:
    #: plane name -> {"ops": [(name, start_ns, dur_ns)], "modules": [...],
    #: "async": [...]}
    devices: dict[str, dict[str, list]] = field(default_factory=dict)
    #: [(name, start_ns, dur_ns)] host spans, name starts with ``bench/``
    host: list = field(default_factory=list)

    def to_json(self) -> dict:
        return {"devices": {p: {k: [list(e) for e in v]
                                for k, v in lines.items()}
                            for p, lines in self.devices.items()},
                "host": [list(e) for e in self.host]}

    @classmethod
    def from_json(cls, obj: dict) -> "RawTrace":
        return cls(devices={p: {k: [tuple(e) for e in v]
                                for k, v in lines.items()}
                            for p, lines in obj["devices"].items()},
                   host=[tuple(e) for e in obj.get("host", [])])


def load_xplane(path: str) -> RawTrace:
    from jax.profiler import ProfileData
    raw = RawTrace()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            lines = {"ops": [], "modules": [], "async": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules",
                       ASYNC_LINE: "async"}.get(line.name)
                if key is None:
                    continue
                cut = str if key == "modules" else instruction_name
                lines[key] = [(cut(e.name), float(e.start_ns),
                               float(e.duration_ns)) for e in line.events]
            if lines["ops"]:
                raw.devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        raw.host.append((e.name, float(e.start_ns),
                                         float(e.duration_ns)))
    raw.host.sort(key=lambda e: e[1])
    return raw


# ------------------------------------------------------- interval arithmetic

def union(intervals) -> list[tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a, b) -> list[tuple[float, float]]:
    """The parts of the disjoint sorted intervals ``a`` that no interval of
    the disjoint sorted ``b`` covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def collective_intervals(ops) -> list[tuple[float, float]]:
    """When a collective is in flight: a synchronous one for its own
    event, an asynchronous one from the start of its ``-start`` to the end
    of its ``-done`` (paired first-in first-out per kind)."""
    out, open_starts = [], {}
    for name, start, dur in sorted(ops, key=lambda e: e[1]):
        m = COLLECTIVE.match(name.rsplit("/", 1)[-1].lstrip("%"))
        if not m:
            continue
        kind, phase = m.group(1), m.group(2)
        if phase == "-start":
            open_starts.setdefault(kind, []).append(start)
        elif phase == "-done":
            begun = open_starts.get(kind)
            out.append((begun.pop(0) if begun else start, start + dur))
        else:
            out.append((start, start + dur))
    return out


def is_collective(name: str) -> bool:
    return bool(COLLECTIVE.match(name.rsplit("/", 1)[-1].lstrip("%")))


# ------------------------------------------------------------------ reduce

@dataclass
class ChipSummary:
    plane: str
    window_ns: float
    busy_ns: float
    op_groups: dict[str, float]          # group -> summed ns (self time)
    module_op_groups: dict[str, float]   # "<module>:<group>" -> summed ns
    modules: dict[str, tuple[int, float]]  # module -> (launches, ns)
    collective_inflight_ns: float
    collective_exposed_ns: float
    gaps: list[tuple[float, float, str]]   # (start_ns, dur_ns, host span)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_ns / self.window_ns if self.window_ns else 0.0


@dataclass
class ReducedTrace:
    window: tuple[float, float]
    chips: list[ChipSummary]
    host: list                             # the host spans inside the window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the chips used."""
        return sum(c.busy_ns for c in self.chips) / len(self.chips) / 1e9

    def worst(self, attr: str) -> float:
        return max(getattr(c, attr) for c in self.chips)

    def group_ns(self, pattern: str) -> float:
        """Summed time of the op groups matching ``pattern``, averaged
        over chips."""
        rx = re.compile(pattern)
        return sum(ns for c in self.chips for g, ns in c.op_groups.items()
                   if rx.search(g)) / len(self.chips)

    def breakdown(self, top: int = 10, aliases: dict | None = None) -> dict:
        """The contract's ``breakdown``: the device op groups that took
        most time, each under the program it ran in
        (``jit_step:splash_mha_fwd``; ``aliases`` from ``alias_modules`` name
        the programs that have no name), and the longest idle gaps by what
        the host was doing, both on the chip with the least busy time, in
        seconds."""
        chip = min(self.chips, key=lambda c: c.busy_ns)
        named: dict[str, float] = {}
        for key, ns in chip.module_op_groups.items():
            mod, _, group = key.rpartition(":")
            key = f"{(aliases or {}).get(mod, mod)}:{group}"
            named[key] = named.get(key, 0.0) + ns
        ops = sorted(named.items(), key=lambda kv: -kv[1])[:top]
        by_span: dict[str, float] = {}
        for _, dur, span in chip.gaps:
            by_span[span] = by_span.get(span, 0.0) + dur
        gaps = sorted(by_span.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[g, ns / 1e9] for g, ns in ops],
                "idle_gaps": [[s, ns / 1e9] for s, ns in gaps]}


def _self_times(ops) -> list[tuple[str, float, float, float]]:
    """(name, start, dur, self_ns) per event: an event's self time is its
    duration minus what the events nested inside it cover.  Events of one
    line nest properly, so a stack does it in one pass."""
    out = []
    stack: list[list] = []       # [name, start, end, covered, index]
    for name, start, dur in sorted(ops, key=lambda e: (e[1], -e[2])):
        end = start + dur
        while stack and stack[-1][2] <= start:
            stack.pop()
        if stack:
            out[stack[-1][3]][3] -= min(end, stack[-1][2]) - start
        out.append([name, start, dur, dur])
        stack.append([name, start, end, len(out) - 1])
    return [tuple(e) for e in out]


def _attribute(gap_start: float, gap_end: float, host) -> str:
    """The host span that covers most of the gap; among spans nested in
    one another the innermost (shortest) wins.  ``(none)`` when no span
    was open: the host was outside anything the runner named."""
    best, best_key = "(none)", (0.0, 0.0)
    for name, start, dur in host:
        if name == WINDOW_SPAN:
            continue
        ov = min(gap_end, start + dur) - max(gap_start, start)
        if ov <= 0:
            continue
        key = (ov, -dur)
        if key > best_key:
            best, best_key = name, key
    return best


def reduce(raw: RawTrace, *, top_gaps: int = 50,
           min_gap_ns: float = 1e3) -> ReducedTrace:
    """Reduce over the window the runner marked (``bench/window``), or the
    extent of the device events when there is no such span."""
    marks = [e for e in raw.host if e[0] == WINDOW_SPAN]
    if marks:
        lo, hi = marks[0][1], marks[0][1] + marks[0][2]
    else:
        evs = [e for d in raw.devices.values() for e in d["ops"]]
        lo = min(e[1] for e in evs)
        hi = max(e[1] + e[2] for e in evs)
    host = [e for e in raw.host if e[1] + e[2] > lo and e[1] < hi]
    chips = []
    for plane, lines in sorted(raw.devices.items()):
        # every event is cut to the window, so sums and the union agree
        ops = [(n, max(s, lo), min(s + d, hi) - max(s, lo))
               for n, s, d in lines["ops"] if s + d > lo and s < hi]
        leaves = [e for e in ops if not CONTAINERS.match(
            e[0].rsplit("/", 1)[-1].lstrip("%"))]
        busy = clip(union((s, s + d) for _, s, d in leaves), lo, hi)
        launches = sorted((s, s + d, module_group(n))
                          for n, s, d in lines.get("modules", []))
        starts = [m[0] for m in launches]
        groups: dict[str, float] = {}
        by_module: dict[str, float] = {}
        for name, s, d, self_ns in _self_times(leaves):
            g = op_group(name)
            groups[g] = groups.get(g, 0.0) + self_ns
            # the program launch this op ran in, by containment
            i = bisect.bisect_right(starts, s) - 1
            mod = launches[i][2] if i >= 0 and s < launches[i][1] else "?"
            key = f"{mod}:{g}"
            by_module[key] = by_module.get(key, 0.0) + self_ns
        modules: dict[str, tuple[int, float]] = {}
        for name, s, d in lines.get("modules", []):
            if s + d > lo and s < hi:
                g = module_group(name)
                n, t = modules.get(g, (0, 0.0))
                modules[g] = (n + 1, t + min(s + d, hi) - max(s, lo))
        # the async line holds a started operation for as long as it is in
        # flight; the op line holds its -start and -done as they execute
        in_flight = [(s, s + d) for n, s, d in lines.get("async", [])
                     if is_collective(n)]
        coll = clip(union(collective_intervals(leaves) + in_flight), lo, hi)
        compute = clip(union((s, s + d) for n, s, d in leaves
                             if not is_collective(n)), lo, hi)
        exposed = subtract(coll, compute)
        idle = [(s, e) for s, e in subtract([(lo, hi)], busy)
                if e - s >= min_gap_ns]
        idle.sort(key=lambda iv: iv[0] - iv[1])
        gaps = [(s, e - s, _attribute(s, e, host)) for s, e in idle[:top_gaps]]
        chips.append(ChipSummary(
            plane=plane, window_ns=hi - lo, busy_ns=total(busy),
            op_groups=groups, module_op_groups=by_module,
            modules=modules,
            collective_inflight_ns=total(coll),
            collective_exposed_ns=total(exposed), gaps=gaps))
    if not chips:
        raise ValueError("the trace holds no device plane with op events")
    return ReducedTrace(window=(lo, hi), chips=chips, host=host)


def find_xplane(trace_dir: str) -> str:
    import glob
    import os
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def dump(path: str, top: int = 25) -> None:
    """Print a trace's planes, lines and most frequent event names: what to
    read before coding against a new kind of trace."""
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(path).planes:
        print(f"PLANE {plane.name}")
        for line in plane.lines:
            tot: dict[str, list] = {}
            n = 0
            for e in line.events:
                n += 1
                rec = tot.setdefault(op_group(instruction_name(e.name)),
                                     [0, 0.0, e.name[:160]])
                rec[0] += 1
                rec[1] += e.duration_ns
            print(f"  LINE {line.name!r}: {n} events, {len(tot)} groups")
            for g, (cnt, ns, ex) in sorted(tot.items(),
                                           key=lambda kv: -kv[1][1])[:top]:
                print(f"    {ns / 1e6:12.3f} ms {cnt:7d}  {g}   e.g. {ex}")


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace", help=".xplane.pb file, a directory with one, "
                    "or a RawTrace .json")
    ap.add_argument("--dump", action="store_true")
    ap.add_argument("--json", help="write the RawTrace as JSON here")
    args = ap.parse_args(argv)
    import os
    path = find_xplane(args.trace) if os.path.isdir(args.trace) \
        else args.trace
    if args.dump:
        dump(path)
        return 0
    if path.endswith(".json"):       # a RawTrace written by --json
        with open(path) as f:
            raw = RawTrace.from_json(json.load(f))
    else:
        raw = load_xplane(path)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(raw.to_json(), f)
    red = reduce(raw)
    print(json.dumps({"window_s": red.window_s, "busy_s": red.busy_s,
                      "idle_worst": red.worst("idle_share"),
                      **red.breakdown()}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
