"""Shared by the readers of the host-device crossings: every device idle gap
inside a ``serve/round`` is put down to the crossing that caused it, on the
clock the host's spans and the chip's events share.

The engine opens one ``serve/launch_dispatch`` span round every call of a
compiled program (attributes ``program``, ``k``), its sync and stage spans
say how many arrays they moved (``arrays``, ``bytes``), and the pump's
``pump/resolve`` how many retired losses it read back (``reads``).  On the
idlest chip, a gap ``[g0, g1]`` inside a round splits, in this order:

  wake            from ``g0`` to the end of the host wait (``pump/<reason>``
                  or ``serve/*_sync``) that was already open at ``g0``: the
                  chip is done and the host has not been released
  launch latency  from the start of the launch whose program ends the gap
                  (never before the wake's end) to ``g1``: the host has
                  called the program and the chip has not begun it
  read            what lies between those two under ``serve/*_sync`` and
                  ``pump/resolve`` spans that opened after ``g0``: further
                  blocking reads of a chip that is already idle
  host work       the rest: ``serve/bookkeep``, ``serve/admit``, ``*_stage``,
                  Python between launches; also given by innermost span

The four sum to ``_scopes.round_idle_ms`` by construction.  A gap is also
counted as a BUBBLE when it lies inside one program's event on the chip's
module line (op to op, nothing a host can hide); bubbles stay in the part
the rules above give them and are reported beside it.

Which program ends a gap: the n-th ``serve/launch_dispatch`` of the trace
belongs to the n-th event of the chip's module line (``_pair``; a traced
window opens on a drained engine).  Where the counts do not fit (a
speculative engine stacks its draft tokens by eager calls that are no span)
the gap goes to the last launch span that begins before ``g1`` and is still
open after ``g0``.  The pairing also names each program by its launches'
``program`` attribute; ``reduce_trace.alias_modules`` guesses the same from
launch counts, and a line on stderr says when the guess is wrong: the
readers that go through ``_programs.py`` then have the engine's programs
swapped in that run.

The one clock is one to about a millisecond (looked at by hand, PR 35: in
one trace every program began 0.14 to 0.73 ms BEFORE its launch span
opened, in the next 0.00 to 0.49 ms after): the profiler aligns the chip's
clock with the host's that well and no better, and a millisecond is the
size of what is split here.  ``_clock_offset`` bounds the chip's lead by
causality and the split is made at the middle of the bounds.  What a gap
holds between two HOST events (read, host work) does not depend on it, nor
does wake + launch latency; how that sum divides does, by half the bounds'
width a crossing, which the report prints.

``_scopes.load`` keeps a span's name and times; its attributes are event
stats, read here.

    python benchmarks/layer_metrics/_crossings.py <trace.xplane.pb | raw.json>
"""

from __future__ import annotations

import bisect
import json
import re
import sys
from dataclasses import dataclass, field
from pathlib import Path
from types import SimpleNamespace

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks import harness, reduce_trace as R  # noqa: E402
from benchmarks.layer_metrics import _scopes as S  # noqa: E402

LAUNCH_SPAN = "serve/launch_dispatch"
#: spans under which the host reads arrays back, one blocking read each
#: (attribute ``arrays``, the pump's ``reads``), and spans in which it waits
SYNC = re.compile(r"^(serve/[a-z]+_sync|pump/resolve)$")
WAIT = re.compile(r"^(pump/(?!resolve$)[a-z_]+|serve/[a-z]+_sync)$")
STAGE = re.compile(r"^serve/[a-z]+_stage$")
#: host waits that end only when the LAST program launched before them has
#: (``pump/throttle`` and ``pump/drain`` wait for an older one)
AWAITS_LAST = re.compile(
    r"^(pump/(sync_every|per_step|profile_boundary)|serve/[a-z]+_sync)$")
PARTS = ("wake", "read", "hostwork", "launch_latency")
#: a gap that no launch ended: a bubble, or the round's own end
NO_LAUNCH = ("(no launch)", -1)


def span_attrs(path: str) -> dict[tuple[str, float], dict]:
    """``(name, start_ns)`` -> attributes of every ``serve/`` and ``pump/``
    span of a trace; a ``ScopedRaw`` JSON carries them under ``span_attrs``
    as ``[name, start_ns, {...}]``."""
    if str(path).endswith(".json"):
        rows = json.loads(Path(path).read_text()).get("span_attrs", [])
        return {(n, float(s)): dict(a) for n, s, a in rows}
    from jax.profiler import ProfileData
    out = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(("serve/", "pump/")):
                        out[(e.name, float(e.start_ns))] = dict(e.stats)
    return out


@dataclass
class Crossings:
    rounds: int
    #: part -> idle ns inside ``serve/round`` on the idlest chip
    parts: dict[str, float]
    #: part -> the share of it that is bubbles inside a running program
    bubbles: dict[str, float]
    #: (program, k) of the launch whose program ended a gap -> the gaps' ns
    by_launch: dict[tuple[str, int], float]
    #: arrays read under the sync spans that "read" idle fell under
    reads: int
    #: bounds on how far the chip's clock leads the host's, ns
    #: (``_clock_offset``); the split is made at their middle
    clock: tuple[float, float]
    #: the host-work part by the innermost program span it fell under
    hostwork_by_span: dict[str, float]
    #: module (``reduce_trace.module_group``) -> program, by launch spans;
    #: empty when launches and module events could not be paired
    programs: dict[str, str]
    #: the window's launches by program, and its modules as
    #: ``alias_modules`` wants them (name -> (launches, ns))
    launches: dict[str, int]
    modules: dict[str, tuple[int, float]]
    #: name -> (spans, sum of ``arrays``, sum of ``bytes``) of the window's
    #: stage and sync spans, and the means of ``live`` and ``rows``
    moved: dict[str, tuple[int, int, int]] = field(default_factory=dict)
    carried: dict[str, float] = field(default_factory=dict)

    @property
    def total_ns(self) -> float:
        return sum(self.parts.values())

    def part_ms(self, part: str) -> float:
        return self.parts[part] / 1e6 / self.rounds

    def swapped(self, launches: dict[str, int] | None = None,
                red=None) -> str | None:
        """One line when ``reduce_trace.alias_modules`` names a program
        otherwise than its launch spans do, else None.  ``red`` and
        ``launches`` are the run's own (``ctx.trace`` and the runner's
        ``program_launches``); without them, this trace's, under the two
        labels and in the order the runner counts them."""
        red = red or SimpleNamespace(
            chips=[SimpleNamespace(modules=self.modules)])
        guess = R.alias_modules(red, launches or {
            k: self.launches[k] for k in ("decode", "prefill")
            if k in self.launches})
        wrong = {m: (lab, self.programs[m]) for m, lab in guess.items()
                 if self.programs.get(m, lab) != lab}
        if not wrong:
            return None
        said = ", ".join(f"{m} is {mine!r} by its launch spans and {lab!r} "
                         f"by its launch count"
                         for m, (lab, mine) in sorted(wrong.items()))
        return (f"[bench] program names disagree: {said}: the readers that "
                f"go through layer_metrics/_programs.py have the engine's "
                f"programs swapped in this run")

    def report(self) -> str:
        r = self.rounds
        ms = lambda ns: ns / 1e6 / r  # noqa: E731
        lines = [f"[bench] device idle inside serve/round by crossing, ms a "
                 f"round over {r} round(s) (total {ms(self.total_ns):.3f}):"]
        for part in PARTS:
            lines.append(
                f"[bench]   {part:<16} {ms(self.parts[part]):8.3f}"
                f"   of it bubbles inside a program "
                f"{ms(self.bubbles[part]):.3f}")
        lo, hi = self.clock
        lines.append(
            f"[bench]   the chip's clock leads the host's by {lo / 1e6:.3f} to "
            f"{hi / 1e6:.3f} ms (causality; split at the middle): wake and "
            f"launch_latency are each known to +-{(hi - lo) / 2e6:.3f} ms a "
            f"crossing, their sum exactly")
        lines.append("[bench]   hostwork by innermost span: " + ", ".join(
            f"{name.removeprefix('serve/')} {ms(ns):.3f}" for name, ns in
            sorted(self.hostwork_by_span.items(), key=lambda kv: -kv[1])))
        if self.reads:
            lines.append(f"[bench]   {self.parts['read'] / 1e6 / self.reads:.3f}"
                         f" ms a read over {self.reads} read(s) of an idle "
                         f"chip, {self.reads / r:.2f} a round")
        lines.append("[bench] idle by the launch whose program ended the "
                     "gap, ms a round:")
        for (prog, k), ns in sorted(self.by_launch.items(),
                                    key=lambda kv: -kv[1]):
            lines.append(f"[bench]   {prog:>12} k={k:<3} {ms(ns):8.3f}")
        for name, (n, arrays, nbytes) in sorted(self.moved.items()):
            lines.append(f"[bench]   {name:<22} {n / r:6.2f} a round, "
                         f"{arrays / r:6.2f} arrays, {nbytes / r:10.0f} B")
        for name, mean in sorted(self.carried.items()):
            lines.append(f"[bench]   {name:<22} mean {mean:.2f}")
        named = ", ".join(f"{m} = {p}" for m, p in sorted(self.programs.items()))
        lines.append(f"[bench] programs by launch span: {named or 'not paired'}"
                     f" ({sum(self.launches.values())} launches)")
        return "\n".join(lines)


def _reads(a: dict) -> int:
    """The arrays a sync span read: ``arrays``, or the pump's ``reads``."""
    return int(a.get("arrays", a.get("reads", 1)))


def _pair(launches, modules) -> dict[int, int]:
    """module index -> launch index: the n-th launch span is the n-th
    event of the module line; of its UNNAMED programs alone where the
    line holds others (a scalar the engine puts is a one-microsecond
    ``jit_convert_element_type``, and no launch of the engine's).  Empty
    when neither count fits."""
    for keep in (lambda name: True,
                 lambda name: "(" in name):    # module_group kept its id
        idx = [i for i, m in enumerate(modules) if keep(m[2])]
        if len(idx) == len(launches):
            return dict(zip(idx, range(len(launches))))
    return {}


def _clock_offset(launches, modules, pair, main) -> tuple[float, float]:
    """How far the chip's stamps lead the host's, ns, as the bounds
    causality gives: no program begins on the chip before the host began
    to launch it (above), and no host wait for a program ends before the
    program does (below).  A profiler aligns the two clocks to about a
    millisecond, which is the size of what is split here; between the
    bounds lies the shortest launch-to-release round trip of the trace,
    and how it divides into launch latency and wake cannot be read."""
    if not pair:
        return 0.0, 0.0
    hi = min(modules[m][0] - launches[i][0] for m, i in pair.items())
    module_of = {i: m for m, i in pair.items()}
    starts = [x[0] for x in launches]
    lo = -float("inf")
    for name, s, d in main:
        if AWAITS_LAST.match(name):
            i = bisect.bisect_left(starts, s) - 1
            if i >= 0:
                lo = max(lo, modules[module_of[i]][1] - (s + d))
    # no wait to bound it from below: the clocks are taken to agree
    # unless causality says the chip's leads by less than nothing
    return (lo, hi) if lo > -float("inf") else (min(hi, 0.0),) * 2


def reduce(raw: S.ScopedRaw, attrs: dict) -> Crossings | None:
    """None when the window holds no ``serve/round``, or no launch is a
    span (a program from before the launch spans: nothing says which
    crossing a gap waited for)."""
    # the main thread, the window, the rounds that begin in it and the
    # idlest chip's idle intervals, chosen as ``_scopes.reduce`` chooses
    # them (its own pass over every op costs seconds and gives no interval)
    main = next((t for t in raw.threads
                 if any(e[0] == R.WINDOW_SPAN for e in t)), None)
    if main is not None:
        lo, dur = next(e[1:] for e in main if e[0] == R.WINDOW_SPAN)
        hi = lo + dur
    else:
        main = max(raw.threads, key=len, default=[])
        evs = [e for d in raw.devices.values() for e in d["ops"]]
        lo = min(e[1] for e in evs)
        hi = max(e[1] + e[2] for e in evs)
    begun = [n for n, s, _ in main if lo <= s < hi]
    rounds = begun.count(S.ROUND)
    if not rounds or LAUNCH_SPAN not in begun:
        return None
    idle, lines = [], None
    for plane in sorted(raw.devices):
        ops = raw.devices[plane]["ops"]
        free = R.subtract([(lo, hi)], R.clip(R.union(
            (s, s + d) for n, s, d, _ in ops
            if not R.CONTAINERS.match(n)), lo, hi))
        if lines is None or R.total(free) > R.total(idle):
            idle, lines = free, raw.devices[plane]
    in_round = R.clip(R.union((s, s + d) for n, s, d in main
                              if n == S.ROUND), lo, hi)
    gaps = R.subtract(idle, R.subtract(idle, in_round))   # idle and in_round

    launches = sorted((s, s + d, attrs.get((n, s), {}))
                      for n, s, d in main if n == LAUNCH_SPAN)
    modules = sorted((s, s + d, R.module_group(n))
                     for n, s, d in lines.get("modules", []))
    module_starts = [m[0] for m in modules]
    pair = _pair(launches, modules)
    clock = _clock_offset(launches, modules, pair, main)
    # the host's spans on the chip's clock, at the middle of the bounds
    lead = (clock[0] + clock[1]) / 2
    waits = sorted((s + lead, s + d + lead) for n, s, d in main
                   if WAIT.match(n))
    wait_starts = [w[0] for w in waits]
    syncs = sorted((s + lead, s + d + lead, _reads(attrs.get((n, s), {})))
                   for n, s, d in main if SYNC.match(n))
    sync_starts = [y[0] for y in syncs]
    launch_starts = [x[0] + lead for x in launches]
    pieces = [(s + lead, e + lead, stack) for s, e, stack in S.segments(
        [e for e in main if e[0].startswith(S.PROGRAM_SPANS)])]
    piece_starts = [p[0] for p in pieces]

    parts = dict.fromkeys(PARTS, 0.0)
    bubbles = dict.fromkeys(PARTS, 0.0)
    by_launch: dict[tuple[str, int], float] = {}
    by_span: dict[str, float] = {}
    read_under: set[int] = set()
    for g0, g1 in gaps:
        m = bisect.bisect_right(module_starts, g1) - 1
        bubble = m >= 0 and modules[m][0] <= g0 and g1 <= modules[m][1]
        # wake: the wait open at g0 (waits do not nest)
        wake_end = g0
        w = bisect.bisect_right(wait_starts, g0) - 1
        if w >= 0 and waits[w][1] > g0:
            wake_end = min(waits[w][1], g1)
        # the launch whose program begins at g1
        i = None
        if pair:
            if m in pair and modules[m][0] > g0:
                i = pair[m]
        else:
            i = bisect.bisect_left(launch_starts, g1) - 1
            if i < 0 or launches[i][1] + lead <= g0:
                i = None
        late_from = g1 if i is None \
            else min(max(launch_starts[i], wake_end), g1)
        # read: syncs that opened after g0, between the wake and the launch
        read = 0.0
        for y in range(bisect.bisect_right(sync_starts, g0), len(syncs)):
            if syncs[y][0] >= late_from:
                break
            got = min(late_from, syncs[y][1]) - max(wake_end, syncs[y][0])
            if got > 0:
                read += got
                read_under.add(y)
        took = {"wake": wake_end - g0, "read": read,
                "launch_latency": g1 - late_from,
                "hostwork": late_from - wake_end - read}
        for part, ns in took.items():
            parts[part] += ns
            if bubble:
                bubbles[part] += ns
        # the host work by what the host was in: every span between the
        # wake and the launch but the syncs counted as reads
        j = max(bisect.bisect_right(piece_starts, wake_end) - 1, 0)
        while j < len(pieces) and pieces[j][0] < late_from:
            s, e, stack = pieces[j]
            got = min(late_from, e) - max(wake_end, s)
            if got > 0 and not any(SYNC.match(n) for n in stack):
                by_span[stack[-1]] = by_span.get(stack[-1], 0.0) + got
            j += 1
        key = NO_LAUNCH if i is None else (
            str(launches[i][2].get("program", "?")),
            int(launches[i][2].get("k", -1)))
        by_launch[key] = by_launch.get(key, 0.0) + g1 - g0

    votes: dict[str, dict[str, int]] = {}
    for m, i in pair.items():
        prog = str(launches[i][2].get("program", "?"))
        tally = votes.setdefault(modules[m][2], {})
        tally[prog] = tally.get(prog, 0) + 1
    window_launches: dict[str, int] = {}
    for s, _, a in launches:
        if lo <= s < hi:
            prog = str(a.get("program", "?"))
            window_launches[prog] = window_launches.get(prog, 0) + 1
    window_modules: dict[str, tuple[int, float]] = {}
    for s, e, mod in modules:
        if e > lo and s < hi:
            n, ns = window_modules.get(mod, (0, 0.0))
            window_modules[mod] = (n + 1, ns + min(e, hi) - max(s, lo))
    moved, carried = _carried(main, attrs, lo, hi)
    return Crossings(
        rounds=rounds, parts=parts, bubbles=bubbles, by_launch=by_launch,
        reads=sum(syncs[y][2] for y in read_under), clock=clock,
        hostwork_by_span=by_span,
        programs={mod: max(v, key=v.get) for mod, v in votes.items()},
        launches=window_launches, modules=window_modules, moved=moved,
        carried=carried)


def _carried(main, attrs: dict, lo: float, hi: float):
    """What the window's stage and sync spans moved (name -> spans, arrays,
    bytes) and the mean work its dispatch spans carried (``live``,
    ``rows``): the attributes the engine records where it knows them."""
    moved: dict[str, tuple[int, int, int]] = {}
    carried: dict[str, list] = {}
    for n, s, _ in main:
        if not lo <= s < hi:
            continue
        a = attrs.get((n, s), {})
        if SYNC.match(n) or STAGE.match(n):
            cnt, arrays, nbytes = moved.get(n, (0, 0, 0))
            moved[n] = (cnt + 1, arrays + (_reads(a) if a else 0),
                        nbytes + int(a.get("bytes", 0)))
        for key in ("live", "rows"):
            if key in a:
                carried.setdefault(f"{n} {key}", []).append(float(a[key]))
    return moved, {k: sum(v) / len(v) for k, v in carried.items()}


# --------------------------------------------------------- for the readers

_TABLES: dict[str, Crossings | None] = {}


def table(ctx) -> Crossings | None:
    """The traced run's split, loaded once per process and printed to
    stderr when it is; None when the run was not traced or ``reduce``
    finds nothing to split."""
    if ctx.trace is None:
        return None
    path = R.find_xplane(str(harness.OUT / "trace"))
    if path not in _TABLES:
        tab = _TABLES[path] = reduce(S.load(path), span_attrs(path))
        if tab is not None:
            print(tab.report(), file=sys.stderr)
            line = tab.swapped(ctx.counters.get("program_launches"),
                               ctx.trace)
            if line:
                print(line, file=sys.stderr)
    return _TABLES[path]


def round_part_ms(ctx, part: str) -> float | None:
    """Device idle ms a round inside ``serve/round`` that the split puts
    down to ``part``."""
    tab = table(ctx)
    return tab.part_ms(part) if tab else None


def per_round(ctx, counter: str) -> float | None:
    """An engine counter of the window per round; None on a program
    without it."""
    s = ctx.counters["stats"]
    if s.get(counter) is None or not s.get("rounds"):
        return None
    return s[counter] / s["rounds"]


def main(argv=None) -> int:
    path = (argv or sys.argv[1:])[0]
    tab = reduce(S.load(path), span_attrs(path))
    if tab is None:
        print(f"the trace's window holds no {S.ROUND} with a {LAUNCH_SPAN}",
              file=sys.stderr)
        return 1
    print(tab.report())
    line = tab.swapped()
    if line:
        print(line, file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
