"""Shared by the readers of the program's own names: device time per
``jax.named_scope`` of the program's catalogue, and device idle time per
program span (``serve/``, ``pump/``, ``prefetch/``, ``checkpoint/``) open on
the host's main thread.  ``reduce_trace`` keeps an event's instruction name
only; this helper reads the same ``.xplane.pb`` once more for what the
program wrote into it, and reuses ``reduce_trace``'s interval arithmetic.

Where a v5e trace carries a scope path (jax 0.9, looked at by hand, PR 23):
NOT on the op event.  ``jax.profiler.ProfileData`` shows an op event's own
stats only (``device_offset_ps``, ``device_duration_ps``), and the event's
name is the instruction's text without its ``metadata={...}``.  The path is
a stat of the event's METADATA record in the raw ``XPlane.event_metadata``
map: ``tf_op`` =
``jit(step)/forward_backward/transpose(jvp())/while/body/closed_call/
checkpoint/rematted_computation/mlp/dot_general:`` (beside ``hlo_category``,
``flops``, ``bytes_accessed``, ``source``).  ``ProfileData`` does not expose
that map, so ``op_paths`` decodes it from the file's protobuf wire format
(a few lines; no TensorFlow import) and the join is by the event's name,
which is the metadata record's name.  No join against compiled HLO text is
needed.  A fusion carries the path of its root instruction, so elementwise
work fused across a scope boundary counts under the scope of the fusion's
root.  The path is the one the executable was COMPILED with: JAX's
persistent compilation cache leaves metadata out of its key, so a program
loaded from an entry that an older source compiled shows that source's
scopes (none, if it had none) until the program itself changes.  Host spans are ``TraceAnnotation`` events on the lines of
``/host:CPU``, one line per thread (two may share the name ``python``);
their attributes are event stats.  The main thread is the line that holds
``bench/window``.

    python benchmarks/layer_metrics/_scopes.py <trace.xplane.pb | raw.json>
"""

from __future__ import annotations

import bisect
import re
import sys
from dataclasses import dataclass, field
from pathlib import Path

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks import harness, reduce_trace as R  # noqa: E402

#: ``distributed_training_sandbox_tpu/utils/profiling.py`` ``SCOPES``,
#: copied; a test holds the copy to the original
CATALOGUE = (
    "embed", "attn_qkv", "attn_core", "attn_out", "mlp", "loss_head",
    "kv_write", "kv_gather", "sample",
    "fsdp_layer_gather", "fsdp_root_gather", "fsdp_pre_gather_layers",
    "loss_mean", "grad_mean", "opt_step",
)
PROGRAM_SPANS = ("serve/", "pump/", "prefetch/", "checkpoint/")
ROUND = "serve/round"
#: the host preparing and launching work / reading results and updating state
LAUNCH = re.compile(r"^serve/(admit|[a-z]+_stage|[a-z]+_dispatch)$")
READBACK = re.compile(r"^serve/([a-z]+_sync|bookkeep)$")
NO_SCOPE = "(no scope)"
NO_SPAN = "(no span)"

_WORD = re.compile(r"[A-Za-z0-9_]+")
_NAMES = frozenset(CATALOGUE)


def innermost(path: str | None) -> str | None:
    """The innermost catalogue name anywhere in an op's scope path, so that
    ``transpose(jvp(mlp))/dot_general`` and
    ``checkpoint/rematted_computation/mlp/mul`` count under ``mlp``."""
    for word in reversed(_WORD.findall(path or "")):
        if word in _NAMES:
            return word
    return None


# ------------------------------------------------- the raw protobuf, by hand

def _varint(b, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, a memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wire in (1, 5):
            ln = 8 if wire == 1 else 4
            v, i = b[i:i + ln], i + ln
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v


def _map_entry(b) -> tuple[int, memoryview]:
    key, value = 0, memoryview(b"")
    for num, v in _fields(b):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


def op_paths(xspace: bytes, stat: str = "tf_op") -> dict[str, dict[str, str]]:
    """``{plane name: {event name: scope path}}`` of every device plane of a
    serialized ``XSpace``.  Field numbers are ``xplane.proto``'s: XSpace
    planes=1; XPlane name=2, event_metadata=4, stat_metadata=5;
    XEventMetadata name=2, stats=5; XStatMetadata name=2; XStat
    metadata_id=1, str_value=5, ref_value=7 (a string interned as the name
    of another XStatMetadata)."""
    out: dict[str, dict[str, str]] = {}
    for num, plane in _fields(memoryview(xspace)):
        if num != 1:
            continue
        name, stat_names, events = "", {}, []
        for n, v in _fields(plane):
            if n == 2:
                name = bytes(v).decode()
            elif n == 5:
                key, meta = _map_entry(v)
                stat_names[key] = next(
                    (bytes(x).decode() for f, x in _fields(meta) if f == 2),
                    "")
            elif n == 4:
                events.append(_map_entry(v)[1])
        if not name.startswith("/device:"):
            continue
        paths = out.setdefault(name, {})
        for meta in events:
            ev_name, path = "", None
            for f, x in _fields(meta):
                if f == 2:
                    ev_name = bytes(x).decode(errors="replace")
                elif f == 5:
                    st = dict(_fields(x))
                    if stat_names.get(st.get(1)) != stat:
                        continue
                    path = bytes(st[5]).decode(errors="replace") \
                        if 5 in st else stat_names.get(st.get(7))
            if path:
                paths.setdefault(ev_name, path.rstrip(":"))
    return out


# ---------------------------------------------------------------- raw form

@dataclass
class ScopedRaw:
    """What the reduction needs of a trace; round-trips through JSON, which
    is what ``fixtures/`` holds."""
    #: plane -> {"ops": [(instruction name, start_ns, dur_ns, scope path)],
    #: "modules": [(name, start_ns, dur_ns)]}
    devices: dict[str, dict[str, list]] = field(default_factory=dict)
    #: one list of (name, start_ns, dur_ns) per host thread that recorded a
    #: program span or a ``bench/`` span
    threads: list[list] = field(default_factory=list)

    def to_json(self) -> dict:
        return {"devices": {p: {k: [list(e) for e in v]
                                for k, v in lines.items()}
                            for p, lines in self.devices.items()},
                "threads": [[list(e) for e in t] for t in self.threads]}

    @classmethod
    def from_json(cls, obj: dict) -> "ScopedRaw":
        return cls(devices={p: {k: [tuple(e) for e in v]
                                for k, v in lines.items()}
                            for p, lines in obj["devices"].items()},
                   threads=[[tuple(e) for e in t] for t in obj["threads"]])


def load(path: str) -> ScopedRaw:
    """A profiler's ``.xplane.pb``, or a ``ScopedRaw`` written as JSON."""
    if str(path).endswith(".json"):
        import json
        return ScopedRaw.from_json(json.loads(Path(path).read_text()))
    from jax.profiler import ProfileData
    data = Path(path).read_bytes()
    paths = op_paths(data)
    raw = ScopedRaw()
    keep = PROGRAM_SPANS + (R.HOST_PREFIX,)
    for plane in ProfileData.from_serialized_xspace(data).planes:
        if plane.name.startswith("/device:"):
            lines: dict[str, list] = {"ops": [], "modules": []}
            by_name = paths.get(plane.name, {})
            for line in plane.lines:
                if line.name == R.OPS_LINE:
                    lines["ops"] = [
                        (e.name.partition(" = ")[0].lstrip("%"),
                         float(e.start_ns), float(e.duration_ns),
                         by_name.get(e.name, ""))
                        for e in line.events]
                elif line.name == R.MODULES_LINE:
                    lines["modules"] = [
                        (e.name, float(e.start_ns), float(e.duration_ns))
                        for e in line.events]
            if lines["ops"]:
                raw.devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans = [(e.name, float(e.start_ns), float(e.duration_ns))
                         for e in line.events if e.name.startswith(keep)]
                if spans:
                    raw.threads.append(spans)
    return raw


# ------------------------------------------------------------------ reduce

def segments(spans) -> list[tuple[float, float, tuple[str, ...]]]:
    """One thread's spans, which nest, cut into disjoint pieces, each with
    the stack of spans open during it (outermost first).  A child that
    outlasts its parent by a clock tick is cut to the parent."""
    out: list[tuple[float, float, tuple[str, ...]]] = []
    stack: list[tuple[float, str]] = []       # (end, name)
    cur = 0.0

    def advance(upto: float) -> None:
        nonlocal cur
        if stack and upto > cur:
            out.append((cur, upto, tuple(n for _, n in stack)))
        cur = max(cur, upto)

    for name, start, dur in sorted(spans, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= start:
            advance(stack[-1][0])
            stack.pop()
        advance(start)
        end = min(start + dur, stack[-1][0]) if stack else start + dur
        stack.append((end, name))
    while stack:
        advance(stack[-1][0])
        stack.pop()
    return out


@dataclass
class ChipScopes:
    plane: str
    #: (program, scope) -> self ns inside the window; scope may be NO_SCOPE
    self_ns: dict[tuple[str, str], float]
    idle_ns: float
    #: stack of program spans open on the main thread -> idle ns under it;
    #: the empty stack is idle under no program span
    idle_by_stack: dict[tuple[str, ...], float]


@dataclass
class ScopeTable:
    window: tuple[float, float]
    chips: list[ChipScopes]
    #: main-thread program spans that begin inside the window: name -> count
    span_counts: dict[str, int]

    # ---- device scopes
    def scope_ns(self, scopes, program: str | None = None) -> float:
        """Self time under any of ``scopes``, mean over the chips; of one
        program when ``program`` is given."""
        want = set(scopes)
        return sum(ns for c in self.chips
                   for (prog, scope), ns in c.self_ns.items()
                   if scope in want and program in (None, prog)
                   ) / len(self.chips)

    def busy_self_ns(self) -> float:
        return sum(sum(c.self_ns.values()) for c in self.chips) \
            / len(self.chips)

    # ---- host spans
    @property
    def idlest(self) -> ChipScopes:
        return max(self.chips, key=lambda c: c.idle_ns)

    def idle_ns(self, pick) -> float:
        """Idle ns of the idlest chip under the stacks ``pick(stack)``
        accepts."""
        return sum(ns for stack, ns in self.idlest.idle_by_stack.items()
                   if pick(stack))

    def idle_by_span(self) -> dict[str, float]:
        """Idle ns of the idlest chip by the innermost program span."""
        out: dict[str, float] = {}
        for stack, ns in self.idlest.idle_by_stack.items():
            key = stack[-1] if stack else NO_SPAN
            out[key] = out.get(key, 0.0) + ns
        return out

    def report(self, aliases: dict | None = None) -> str:
        """The whole per-scope and per-span table, for stderr."""
        named = aliases or {}
        rows: dict[tuple[str, str], float] = {}
        for c in self.chips:
            for (prog, scope), ns in c.self_ns.items():
                key = (named.get(prog, prog), scope)
                rows[key] = rows.get(key, 0.0) + ns / len(self.chips)
        busy = sum(rows.values()) or 1.0
        lines = [f"[bench] device self time by program and scope, mean of "
                 f"{len(self.chips)} chip(s), window "
                 f"{(self.window[1] - self.window[0]) / 1e6:.1f} ms:"]
        for (prog, scope), ns in sorted(rows.items(), key=lambda kv: -kv[1]):
            lines.append(f"[bench]   {prog:>12} {scope:<24} "
                         f"{ns / 1e6:10.3f} ms {100 * ns / busy:5.1f}%")
        idle = self.idlest
        lines.append(f"[bench] device idle by innermost program span on the "
                     f"main thread ({idle.plane}, idle "
                     f"{idle.idle_ns / 1e6:.3f} ms):")
        for span, ns in sorted(self.idle_by_span().items(),
                               key=lambda kv: -kv[1]):
            n = self.span_counts.get(span)
            lines.append(f"[bench]   {span:<24} {ns / 1e6:10.3f} ms "
                         f"{100 * ns / (idle.idle_ns or 1.0):5.1f}%"
                         + (f"  ({n} spans)" if n else ""))
        return "\n".join(lines)


def reduce(raw: ScopedRaw) -> ScopeTable:
    """Over the window the runner marked (``bench/window``), as
    ``reduce_trace.reduce`` does: ops cut to the window, containers
    (``while``) left out, an op's self time is its duration less what is
    nested in it, and the program it ran in is the module launch that
    contains its start."""
    main = next((t for t in raw.threads
                 if any(e[0] == R.WINDOW_SPAN for e in t)), None)
    if main is not None:
        mark = next(e for e in main if e[0] == R.WINDOW_SPAN)
        lo, hi = mark[1], mark[1] + mark[2]
    else:
        main = max(raw.threads, key=len, default=[])
        evs = [e for d in raw.devices.values() for e in d["ops"]]
        lo = min(e[1] for e in evs)
        hi = max(e[1] + e[2] for e in evs)
    program = [e for e in main if e[0].startswith(PROGRAM_SPANS)
               and e[1] + e[2] > lo and e[1] < hi]
    pieces = [(max(s, lo), min(e, hi), stack)
              for s, e, stack in segments(program) if e > lo and s < hi]
    counts: dict[str, int] = {}
    for name, start, _ in program:
        if start >= lo:
            counts[name] = counts.get(name, 0) + 1

    chips = []
    for plane, lines in sorted(raw.devices.items()):
        leaves = [(innermost(path) or NO_SCOPE, max(s, lo),
                   min(s + d, hi) - max(s, lo))
                  for n, s, d, path in lines["ops"]
                  if s + d > lo and s < hi and not R.CONTAINERS.match(n)]
        launches = sorted((s, s + d, R.module_group(n))
                          for n, s, d in lines.get("modules", []))
        starts = [m[0] for m in launches]
        self_ns: dict[tuple[str, str], float] = {}
        for scope, s, _, own in R._self_times(leaves):
            i = bisect.bisect_right(starts, s) - 1
            prog = launches[i][2] if i >= 0 and s < launches[i][1] else "?"
            self_ns[(prog, scope)] = self_ns.get((prog, scope), 0.0) + own
        busy = R.clip(R.union((s, s + d) for _, s, d in leaves), lo, hi)
        idle = R.subtract([(lo, hi)], busy)
        by_stack: dict[tuple[str, ...], float] = {}
        j = 0                 # both lists are sorted and disjoint: one sweep
        for s, e, stack in pieces:
            while j < len(idle) and idle[j][1] <= s:
                j += 1
            k = j
            while k < len(idle) and idle[k][0] < e:
                ns = min(idle[k][1], e) - max(idle[k][0], s)
                by_stack[stack] = by_stack.get(stack, 0.0) + ns
                k += 1
        idle_ns = R.total(idle)
        rest = idle_ns - sum(by_stack.values())
        if rest > 0:
            by_stack[()] = rest
        chips.append(ChipScopes(plane=plane, self_ns=self_ns,
                                idle_ns=idle_ns, idle_by_stack=by_stack))
    if not chips:
        raise ValueError("the trace holds no device plane with op events")
    return ScopeTable(window=(lo, hi), chips=chips, span_counts=counts)


# --------------------------------------------------------- for the readers

_TABLES: dict[str, ScopeTable] = {}


def table(ctx) -> ScopeTable | None:
    """The traced run's table, loaded once per process and printed to
    stderr when it is; None when the run was not traced."""
    if ctx.trace is None:
        return None
    path = R.find_xplane(str(harness.OUT / "trace"))
    if path not in _TABLES:
        _TABLES[path] = reduce(load(path))
        print(_TABLES[path].report(programs(ctx)), file=sys.stderr)
    return _TABLES[path]


def programs(ctx) -> dict[str, str]:
    """module -> label of the programs the runner counted launches of (the
    engine's two are unnamed in a trace)."""
    return R.alias_modules(ctx.trace,
                           ctx.counters.get("program_launches", {}))


def scope_ms_per_launch(ctx, scopes, label: str) -> float | None:
    """Self ms under ``scopes`` per launch of the program the runner
    counted under ``label``; None when nothing ran under them."""
    tab = table(ctx)
    mods = [m for m, lab in (programs(ctx) if tab else {}).items()
            if lab == label]
    if not mods:
        return None
    ns = tab.scope_ns(scopes, program=mods[0])
    launches = ctx.trace.chips[0].modules[mods[0]][0]
    return ns / 1e6 / launches if ns else None


def scope_ms_per_step(ctx, scopes) -> float | None:
    """Self ms under ``scopes`` per training step, mean over the chips;
    None when nothing ran under them."""
    tab = table(ctx)
    ns = tab.scope_ns(scopes) if tab else 0.0
    return ns / 1e6 / ctx.counters["steps"] if ns else None


def round_idle_ms(ctx, pick=lambda stack: True) -> float | None:
    """Device idle ms inside ``serve/round`` per round, of the part whose
    innermost ``serve/`` span ``pick(name)`` accepts; None when the trace
    holds no round."""
    tab = table(ctx)
    rounds = tab.span_counts.get(ROUND, 0) if tab else 0
    if not rounds:
        return None

    def inside(stack):
        serve = [n for n in stack if n.startswith("serve/")]
        return ROUND in serve and pick(serve[-1])

    return tab.idle_ns(inside) / 1e6 / rounds


def main(argv=None) -> int:
    print(reduce(load((argv or sys.argv[1:])[0])).report())
    return 0


if __name__ == "__main__":
    sys.exit(main())
