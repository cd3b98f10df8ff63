"""What a profiler trace says about a program's collectives.

Twin of the reference's in-step communication timers
(``zero/zero2.py:91-135,219-228``: cuda-synchronized stopwatches around each
``dist`` call, printed as "communication overhead %").  Under jit there is
nothing to stopwatch: collectives are ops inside one compiled program.  So
everything here is read after the run from the ``.xplane.pb`` that
``jax.profiler`` writes (``plugins/profile/<ts>/*.xplane.pb``), through
``jax.profiler.ProfileData`` and a few lines of protobuf wire format for
the one thing ``ProfileData`` does not hand out (below).  One reader,
:func:`collective_events`; :func:`collective_event_stats` (the
``telemetry.ledger`` join's input) and :func:`split_from_trace` (the
comm/compute split of ``summary.json``) stand on it.

How a trace looks (jax 0.9; a v5e looked at by hand, PRs 22, 23 and 52):

* TPU: one plane a chip, ``/device:TPU:<n>``.  Its line ``XLA Ops`` holds
  one event per executed HLO instruction and the event's NAME IS THE
  INSTRUCTION'S TEXT (``%fusion.365 = bf16[2912,2048]{...} fusion(...),
  kind=kCustom, calls=%all-reduce-scatter.clone``), so the instruction's
  name, its opcode and the computation a fusion calls are all in it; a
  ``while`` is one event that spans its body's events.  The op-name path
  (``tf_op``: ``jit(step)/shard_map/forward_backward/transpose(jvp())/
  while/body/closed_call/checkpoint/fsdp_layer_gather/reduce_scatter``) and
  ``bytes_accessed`` are stats of the event's METADATA record, which
  ``ProfileData`` does not expose: :func:`event_metadata` decodes them from
  the ``XSpace`` bytes.
* CPU simulator: no device plane.  The PjRt CPU client's executor threads
  are lines of ``/host:CPU``; a thunk's event is named after the
  instruction alone (``psum.7``) and carries an ``hlo_op`` stat.  Each such
  line stands in for a plane.  There is no op-name path and no
  ``bytes_accessed`` there: ``scope`` is None and ``bytes`` come from the
  compiled text.

How XLA:TPU runs a collective (the compiled step of the four-chip FSDP
cell, PR 52), and so what counts as one event here:

* a synchronous instruction (``%all-gather.247 = ... all-gather(...)``,
  ``%reduce_scatter.196 = ... reduce-scatter(...)``, ``%psum.7 = ...
  all-reduce(...)``): one event, in flight for its own duration;
* a reduce-scatter emitted as a fusion that ``calls=%all-reduce-scatter.N``
  (an all-reduce and the slice of it, one kernel): one synchronous
  ``reduce_scatter`` event under the FUSION's name;
* an asynchronous pair ``<op>-start`` / ``<op>-done``: one event under the
  start's name, in flight from the start's beginning to the done's end;
* an *async collective fusion*: ``%async-collective-start.N`` (a fusion
  around the collective and an ``AsyncCollectiveStart`` custom call),
  compute fusions that carry the same collective forward
  (``calls=%async_collective_fusion.M``), and ``%async-collective-done.N``.
  One event under the start's name, from the start's beginning to the
  done's end; the fusions between are compute, which is the point of them.

Methodology (honest limits):

* ``exposed_ns`` is the part of an event's in-flight interval in which no
  compute op runs on that chip, compute being every leaf op that is not
  itself a collective's own op.  The two halves of an async collective
  fusion ARE fusions and count as compute while they execute, exactly as
  the benchmark's accepted reader books them
  (``benchmarks/reduce_trace.py``: the yardstick; Σ ``exposed_ns`` of a
  chip equals its ``collective_exposed_ns``).  Time is booked once: where
  two collectives are in flight together, to the one that started first.
* the comm/compute split counts comm hidden under compute as comm
  ("time attributable to", as the reference's blocking timers measured)
  and reports the overlap beside it.
"""

from __future__ import annotations

import glob
import json
import os
import re
from dataclasses import asdict, dataclass
from typing import NamedTuple

from .profiling import SCOPES

OPS_LINE = "XLA Ops"
KINDS = ("all_gather", "reduce_scatter", "all_reduce", "collective_permute",
         "all_to_all")

# ----------------------------------------------------------- finding a trace


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


def latest_xplane_file(trace_dir: str, session: str | None = None) \
        -> str | None:
    """Newest ``*.xplane.pb`` under ``trace_dir`` — or, when ``session``
    names a profiler session directory (absolute, or relative to
    ``trace_dir``), the trace inside exactly that session.  Passing the
    owned session fixes the misattribution hazard of the bare-mtime form:
    a concurrent run or a stale ``profiler_traces/`` entry can be newer
    than the trace this run actually wrote."""
    root = trace_dir
    if session:
        sd = session if os.path.isabs(session) \
            else os.path.join(trace_dir, session)
        if os.path.isdir(sd):
            root = sd
    files = glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


# ------------------------------------------------- the raw protobuf, by hand
#
# ``benchmarks/layer_metrics/_scopes.py`` keeps a copy of these three for its
# string stat until a ``benchmark`` PR moves the benchmark's parses here.

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


def event_metadata(xspace: bytes, stats=("tf_op", "bytes_accessed")) \
        -> dict[str, dict[str, dict]]:
    """``{plane name: {event name: {stat: value}}}`` of every device plane
    of a serialized ``XSpace``, for the string and integer ``stats`` of the
    events' METADATA records.  Field numbers are ``xplane.proto``'s: XSpace
    planes=1; XPlane name=2, event_metadata=4, stat_metadata=5;
    XEventMetadata name=2, stats=5; XStatMetadata name=2; XStat
    metadata_id=1, uint64_value=3, int64_value=4, str_value=5, ref_value=7
    (a string interned as the name of another XStatMetadata)."""
    want = set(stats)
    out: dict[str, dict[str, dict]] = {}
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
        by_event = out.setdefault(name, {})
        for meta in events:
            ev_name, found = "", {}
            for f, x in _fields(meta):
                if f == 2:
                    ev_name = bytes(x).decode(errors="replace")
                elif f == 5:
                    st = dict(_fields(x))
                    stat = stat_names.get(st.get(1))
                    if stat not in want:
                        continue
                    if 5 in st:
                        found[stat] = bytes(st[5]).decode(errors="replace")
                    elif 7 in st:
                        found[stat] = stat_names.get(st[7], "")
                    elif 3 in st or 4 in st:
                        found[stat] = int(st.get(3, st.get(4)))
            if found:
                by_event.setdefault(ev_name, found)
    return out


# ------------------------------------------------------------ the raw form

class Op(NamedTuple):
    """One event of a plane's op line, cut to what the reductions read."""
    instruction: str          # the HLO instruction's name: the join key
    opcode: str               # "fusion", "all-gather-start", ... "" on a CPU
    ref: str                  # the computation a fusion calls, the -start
                              # a -done completes, else ""
    start_ns: float
    dur_ns: float
    path: str                 # the op-name path (``tf_op``), "" if none
    nbytes: int | None        # ``bytes_accessed``, None if the plane has none


# after the shape, the first lower-case word before a "(" is the opcode;
# layouts hold "T(8,128)" and "S(1)", never a lower-case word before "("
_OPCODE = re.compile(r"[\s)}\]]([a-z][a-z\-]*)\(")
CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")


def parse_event_name(text: str) -> tuple[str, str, str]:
    """``(instruction, opcode, ref)`` of an op event's name.  On a TPU the
    name is the instruction's whole text; elsewhere it is the
    instruction's name alone (opcode and ref come back empty), possibly
    behind ``%`` or a ``scope/`` prefix."""
    name, sep, rest = text.partition(" = ")
    if not sep:
        return text.rsplit("/", 1)[-1].lstrip("%"), "", ""
    m = _OPCODE.search(rest)
    opcode = m.group(1) if m else ""
    ref = CALLS_RE.search(rest) if opcode == "fusion" else \
        _OPERAND.search(rest, m.end()) if opcode.endswith("-done") else None
    return name.strip().lstrip("%"), opcode, ref.group(1) if ref else ""


def normalize_event_name(name: str) -> str:
    """Trace event name -> HLO instruction name."""
    return parse_event_name(name)[0]


def load_trace(path: str) -> dict[str, list[Op]]:
    """``{plane: [Op, ...]}`` of a profiler's ``.xplane.pb`` — or of the
    same thing written as JSON by :func:`dump_trace`, which is what the
    tests' recorded fixture is.  A plane is a chip's ``XLA Ops`` line; in a
    trace with no device plane, each executor thread of the CPU client
    that ran HLO thunks (events with an ``hlo_op`` stat)."""
    if str(path).endswith(".json"):
        with open(path) as f:
            obj = json.load(f)
        paths = obj["paths"]
        return {p: [Op(*e[:5], paths[e[5]], e[6]) for e in ops]
                for p, ops in obj["planes"].items()}
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        data = f.read()
    meta = event_metadata(data)
    planes: dict[str, list[Op]] = {}
    host = []
    for plane in ProfileData.from_serialized_xspace(data).planes:
        if plane.name.startswith("/device:"):
            by_name = meta.get(plane.name, {})
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                ops = []
                for e in line.events:
                    st = by_name.get(e.name, {})
                    ops.append(Op(*parse_event_name(e.name),
                                  float(e.start_ns), float(e.duration_ns),
                                  st.get("tf_op", "").rstrip(":"),
                                  st.get("bytes_accessed")))
                if ops:
                    planes[plane.name] = ops
        elif plane.name.startswith("/host:"):
            host.append(plane)
    if planes:
        return planes
    for plane in host:
        for line in plane.lines:
            ops = [Op(*parse_event_name(e.name), float(e.start_ns),
                      float(e.duration_ns), "", None)
                   for e in line.events
                   if any(k == "hlo_op" for k, _ in e.stats)]
            if ops:
                planes[f"{plane.name}/{line.name}"] = ops
    return planes


def dump_trace(planes: dict[str, list[Op]]) -> dict:
    """The JSON form :func:`load_trace` reads back: a row an op, its path
    an index into a table of the distinct ones (they are long and few)."""
    paths = sorted({op.path for ops in planes.values() for op in ops})
    index = {p: i for i, p in enumerate(paths)}
    return {"paths": paths,
            "planes": {p: [[*op[:5], index[op.path], op.nbytes]
                           for op in ops] for p, ops in planes.items()}}


# ------------------------------------------------------- interval arithmetic

def _union(intervals) -> list[tuple[float, float]]:
    """``(start, end)`` intervals merged into disjoint sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _subtract(a, b) -> list[tuple[float, float]]:
    """The parts of the disjoint sorted intervals ``a`` that no interval
    of the disjoint sorted ``b`` covers."""
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


def _total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def interval_overlap_us(comm_iv: list, compute_iv: list) -> float:
    """Total time during which any ``comm`` interval and any ``compute``
    interval (each ``(start, end)``) run concurrently.  Both sides are
    merged first, so stacked fusions and nested collectives don't double-
    count."""
    comm = _union(comm_iv)
    return _total(comm) - _total(_subtract(comm, _union(compute_iv)))


# ------------------------------------------------------ collective events

#: instructions that only contain others: their time is their body's
_CONTAINERS = ("while", "conditional", "call")
_OPCODE_KIND = {"all-gather": "all_gather", "reduce-scatter": "reduce_scatter",
                "all-reduce": "all_reduce",
                "collective-permute": "collective_permute",
                "all-to-all": "all_to_all"}
#: the computation a fusion calls when the fusion IS the collective
_CALLEE_KIND = {"all-reduce-scatter": "reduce_scatter", **_OPCODE_KIND}
#: what XLA names an instruction after when the JAX primitive named it
_PRIMITIVE_KIND = {"all_gather": "all_gather", "psum": "all_reduce",
                   "pmax": "all_reduce", "pmin": "all_reduce",
                   "psum_scatter": "reduce_scatter",
                   "reduce_scatter": "reduce_scatter",
                   "ppermute": "collective_permute",
                   "collective_permute": "collective_permute",
                   "all_to_all": "all_to_all"}
#: the halves of an async collective fusion; ``telemetry.ledger`` reads the
#: same names out of the compiled text
ASYNC_FUSION_RE = re.compile(r"^async-collective-(start|done)([._]\d+)*$")
_NAME = re.compile(
    r"^(" + "|".join(sorted({*_OPCODE_KIND, *_PRIMITIVE_KIND}, key=len,
                            reverse=True))
    + r")(-start|-done)?([._]\d+)*$")
_STEM = re.compile(r"^([a-z\-]+?)(-start|-done)?$")
_WORD = re.compile(r"[A-Za-z0-9_]+")


def classify(op: Op) -> tuple[str | None, str]:
    """``(kind, half)`` of an op event: ``kind`` one of :data:`KINDS` or
    None for an op that is not a collective's own; ``half`` ``"start"`` or
    ``"done"`` for a half of an asynchronous one, else ``""``."""
    m = _STEM.match(op.opcode)
    if m and m.group(1) in _OPCODE_KIND:
        return _OPCODE_KIND[m.group(1)], (m.group(2) or "").lstrip("-")
    if op.opcode == "fusion":
        stem = re.sub(r"([._](\d+|clone))+$", "", op.ref)
        if stem in _CALLEE_KIND:
            return _CALLEE_KIND[stem], ""
        m = ASYNC_FUSION_RE.match(op.instruction)
        # the event does not say which collective the fusion wraps (its
        # path is cut at ``.../while``): an all-gather is the one kind seen
        # wrapped so; the compiled text, where given, has the last word
        return ("all_gather", m.group(1)) if m else (None, "")
    if not op.opcode:                  # a name alone (the CPU simulator)
        m = _NAME.match(op.instruction)
        if m:
            kind = _OPCODE_KIND.get(m.group(1)) or _PRIMITIVE_KIND[m.group(1)]
            return kind, (m.group(2) or "").lstrip("-")
    return None, ""


def innermost_scope(path: str) -> str | None:
    """The innermost name of ``profiling.SCOPES`` anywhere in an op-name
    path, so that ``transpose(jvp(mlp))/dot_general`` counts under ``mlp``."""
    for word in reversed(_WORD.findall(path or "")):
        if word in SCOPES:
            return word
    return None


@dataclass(frozen=True)
class CollectiveEvent:
    """One executed collective on one chip."""
    instruction: str        # the HLO instruction the event is named after
    kind: str               # one of KINDS
    start_ns: float
    inflight_ns: float      # start -> done of an async one, else its own
    exposed_ns: float       # of that, with no compute op on the chip
    bytes: int | None       # the nccl-tests message size (see payload_bytes)
    bytes_source: str | None   # "trace" | "hlo" | None
    scope: str | None       # innermost profiling.SCOPES name in the path
    phase: str              # "bwd" where the path holds transpose(, else fwd

    def to_dict(self) -> dict:
        return asdict(self)


#: messages that ``bytes_accessed`` counts, per (kind, form), ``g`` the
#: group size: a synchronous instruction's operands plus its results; an
#: asynchronous ``-start`` (an async collective fusion's start too)
#: returns its operand beside its result, so the operand counts twice
_ACCESSED_MESSAGES = {
    ("all_gather", "sync"): lambda g: 1 + 1 / g,       # shard in, whole out
    ("reduce_scatter", "sync"): lambda g: 1 + 1 / g,   # whole in, shard out
    ("all_reduce", "sync"): lambda g: 2.0,
    ("collective_permute", "sync"): lambda g: 2.0,
    ("all_to_all", "sync"): lambda g: 2.0,
    ("all_gather", "start"): lambda g: 1 + 2 / g,
    ("collective_permute", "start"): lambda g: 3.0,
    # not seen on a chip; by the same rule
    ("reduce_scatter", "start"): lambda g: 2 + 1 / g,
    ("all_reduce", "start"): lambda g: 3.0,
    ("all_to_all", "start"): lambda g: 3.0,
}


def payload_bytes(kind: str, accessed: int, group: int,
                  asynchronous: bool = False) -> int:
    """The nccl-tests message size (the full logical tensor) of a
    collective from its event's ``bytes_accessed``.  What the stat counts
    was pinned against the shapes of the four-chip FSDP step (PR 52): a
    synchronous all-gather of a ``bf16[128256,2048]`` reads 656,670,720 =
    the shard + the whole; a reduce-scatter (the instruction and the
    all-reduce-scatter fusion alike) the whole + the shard; an all-reduce
    its operand + its result; an async collective fusion's start and a
    ``collective-permute-start`` their operand TWICE + the result (the
    result tuple aliases the operand).  A fusion whose shard is padded
    (``bf16[2912,2048]`` for a quarter of 11008 rows) reads 1% high."""
    form = "start" if asynchronous else "sync"
    return round(accessed / _ACCESSED_MESSAGES[kind, form](group))


def collective_events(xplane_path: str, hlo_text: str | None = None,
                      group: int | None = None,
                      window: tuple[float, float] | None = None) \
        -> dict[str, list[CollectiveEvent]]:
    """``{plane: [CollectiveEvent, ...]}`` in order of start.

    ``bytes`` is the event's ``bytes_accessed`` stat turned into a message
    size (:func:`payload_bytes`; ``group`` is the collective's group size,
    by default the number of planes) where the plane carries the stat, else
    the payload of the site of that name in ``hlo_text`` (the compiled
    program: ``telemetry.ledger.collective_sites``), else None.  With
    ``hlo_text`` a fusion around a collective also gets the collective's
    own kind and scope: the fusion's path is cut where its ops' paths part
    (``.../while``), and without the text an async collective fusion is
    taken for an all-gather, the one kind seen wrapped that way.
    ``window``: count only what lies inside ``(lo, hi)`` ns, events cut to
    it."""
    planes = load_trace(xplane_path)
    group = group or max(len(planes), 1)
    sites = {}
    if hlo_text:
        from ..telemetry.ledger import collective_sites
        sites = {s.name: s for s in collective_sites(hlo_text)}
    return {p: _plane_events(ops, sites, group, window)
            for p, ops in sorted(planes.items())}


def _is_container(op: Op) -> bool:
    return (op.opcode or op.instruction.split(".")[0]) in _CONTAINERS


def _book(flights: list[tuple[float, float]],
          exposed: list[tuple[float, float]]) -> list[float]:
    """Each exposed moment booked to ONE collective: of those in flight,
    the one that started last (a synchronous collective inside an
    asynchronous one's flight is what the chip is waiting on).
    ``flights`` sorted by start, ``exposed`` disjoint and sorted; returns
    the booked ns per flight."""
    import heapq
    out = [0.0] * len(flights)
    active: list[tuple[float, float, int]] = []     # (-start, end, index)
    i = 0
    for a, b in exposed:
        cur = a
        while cur < b:
            while i < len(flights) and flights[i][0] <= cur:
                heapq.heappush(active, (-flights[i][0], flights[i][1], i))
                i += 1
            while active and active[0][1] <= cur:
                heapq.heappop(active)
            # every flight begun by now is in the heap: the next starts later
            upto = min(b, flights[i][0]) if i < len(flights) else b
            if active:
                upto = min(upto, active[0][1])
                out[active[0][2]] += upto - cur
            cur = upto
    return out


def _plane_events(ops: list[Op], sites: dict, group: int,
                  window) -> list[CollectiveEvent]:
    lo, hi = window or (float("-inf"), float("inf"))
    compute, found, open_starts = [], [], {}
    for op in sorted(ops, key=lambda o: o.start_ns):
        if _is_container(op):
            continue
        kind, half = classify(op)
        if kind is None or (half and op.opcode == "fusion"):
            # an async collective fusion's halves are fusions: compute
            # while they execute (the module docstring's yardstick note)
            compute.append((op.start_ns, op.start_ns + op.dur_ns))
        if kind is None:
            continue
        if half == "start":
            open_starts.setdefault(kind, []).append(op)
        elif half == "done":
            # a -done names its -start (TPU); the halves of an async
            # collective fusion share their number; else first in first out
            begun = open_starts.get(kind, [])
            want = op.ref or op.instruction.replace("-done", "-start")
            first = next((b for b in begun if b.instruction == want),
                         begun[0] if begun else op)
            if begun:
                begun.remove(first)
            found.append((first, first.start_ns, op.start_ns + op.dur_ns,
                          kind, True))
        else:
            found.append((op, op.start_ns, op.start_ns + op.dur_ns, kind,
                          False))
    # pairs are made over the whole trace, then cut to the window
    found = sorted((f for f in found if f[2] > lo and f[1] < hi),
                   key=lambda f: f[1])
    flights = [(max(f[1], lo), min(f[2], hi)) for f in found]
    exposed = _book(flights, _subtract(_union(flights), _union(compute)))
    out = []
    for (op, _, _, kind, paired), (s, e), exposed_ns in zip(found, flights,
                                                            exposed):
        site = sites.get(op.instruction)
        if site is not None:
            kind = site.kind
        if op.nbytes is not None:
            nbytes, source = payload_bytes(kind, op.nbytes, group,
                                           paired), "trace"
        elif site is not None:
            nbytes, source = site.payload_bytes, "hlo"
        else:
            nbytes, source = None, None
        path = site.path if site is not None and site.path else op.path
        out.append(CollectiveEvent(
            instruction=op.instruction, kind=kind, start_ns=s,
            inflight_ns=e - s, exposed_ns=exposed_ns, bytes=nbytes,
            bytes_source=source, scope=innermost_scope(path),
            phase="bwd" if "transpose(" in op.path + path else "fwd"))
    return out


# ------------------------------------------------ what stands on the reader

def collective_event_stats(xplane_path: str) -> dict[str, dict]:
    """Per-instruction stats of every collective event in one trace:
    ``{instruction name: {"count", "total_us"}}``.  ``count`` sums across
    planes (chips × invocations), so ``total_us/count`` is the mean
    in-flight time of one chip's participation — the number bandwidth
    math wants."""
    out: dict[str, dict] = {}
    for events in collective_events(xplane_path).values():
        for ev in events:
            rec = out.setdefault(ev.instruction,
                                 {"count": 0, "total_us": 0.0})
            rec["count"] += 1
            rec["total_us"] += ev.inflight_ns / 1e3
    return out


@dataclass
class CommSplit:
    comm_us: float
    compute_us: float
    other_us: float
    trace_file: str
    top_comm: list
    top_compute: list
    # microseconds during which a collective was in flight while a compute
    # op ran on the same chip — the overlap the async collectives, the
    # pump and the prefetcher exist to create
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


def comm_split(planes: dict[str, list[Op]], trace_file: str = "",
               top_n: int = 5) -> CommSplit:
    """The comm/compute split of :func:`load_trace`'s planes, summed over
    them: comm is the union of the collectives' in-flight intervals,
    compute the union of every other leaf op, overlap where both run."""
    comm_by: dict[str, float] = {}
    compute_by: dict[str, float] = {}
    comm_us = compute_us = overlap_us = 0.0
    for ops in planes.values():
        events = _plane_events(ops, {}, 1, None)
        comm = [(ev.start_ns, ev.start_ns + ev.inflight_ns) for ev in events]
        for ev in events:
            comm_by[ev.instruction] = comm_by.get(ev.instruction, 0.0) \
                + ev.inflight_ns / 1e3
        compute = []
        for op in ops:
            if _is_container(op) or classify(op)[0] is not None:
                continue
            compute.append((op.start_ns, op.start_ns + op.dur_ns))
            compute_by[op.instruction] = compute_by.get(
                op.instruction, 0.0) + op.dur_ns / 1e3
        comm_us += _total(_union(comm)) / 1e3
        compute_us += _total(_union(compute)) / 1e3
        overlap_us += interval_overlap_us(comm, compute) / 1e3
    top = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top_n]  # noqa: E731
    return CommSplit(comm_us=comm_us, compute_us=compute_us, other_us=0.0,
                     trace_file=trace_file, top_comm=top(comm_by),
                     top_compute=top(compute_by), overlap_us=overlap_us)


def split_from_trace(trace_dir: str, top_n: int = 5,
                     session: str | None = None) -> CommSplit | None:
    """:func:`comm_split` of the trace under ``trace_dir`` — the one in the
    owned ``session`` directory when given (see
    :func:`latest_xplane_file`), else the newest.  Events that are not HLO
    ops (host threads, dispatch, waits) are in neither bucket and are not
    read (``other_us`` stays 0).  Returns None when no trace exists
    (profiling disabled / single uncaptured step)."""
    tf = latest_xplane_file(trace_dir, session=session)
    if tf is None:
        return None
    return comm_split(load_trace(tf), tf, top_n)


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
