"""Collective-count verification from HLO — the build's upgrade over the
reference's by-eye trace counting.

The reference writes expected NCCL kernel counts in prose and checks profiler
traces manually ("+60 all_reduce +60 broadcast", reference ``README.md:16-20``).
Here the counts are *asserted in pytest*: lower a jitted function, count
collective ops in the StableHLO (pre-optimization — XLA fusion can merge or
reorder them later, SURVEY.md §7.3) and optionally in the compiled HLO.
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass, field
from typing import Callable

import jax

# op-name patterns per collective, for both StableHLO and compiled HLO text.
# Compiled TPU HLO may emit async pairs (`all-reduce-start(...)` +
# `all-reduce-done(...)`); the sync opcode pattern `all-reduce\(` cannot match
# either async form (the char after the opcode stem is `-`, not `(`), so
# counting sync + `-start` sites — and never `-done` — counts each collective
# exactly once in both styles.
_PATTERNS = {
    "all_reduce": [r"stablehlo\.all_reduce",
                   r"\ball-reduce\(", r"\ball-reduce-start\("],
    "all_gather": [r"stablehlo\.all_gather",
                   r"\ball-gather\(", r"\ball-gather-start\("],
    "reduce_scatter": [r"stablehlo\.reduce_scatter",
                       r"\breduce-scatter\(", r"\breduce-scatter-start\("],
    "collective_permute": [r"stablehlo\.collective_permute",
                           r"\bcollective-permute\(",
                           r"\bcollective-permute-start\("],
    "all_to_all": [r"stablehlo\.all_to_all",
                   r"\ball-to-all\(", r"\ball-to-all-start\("],
}


def lowered_text(fn: Callable, *args, optimized: bool = False, **kwargs) -> str:
    """StableHLO (optimized=False) or post-XLA compiled HLO text of ``fn``."""
    lowered = jax.jit(fn).lower(*args, **kwargs)
    if optimized:
        return lowered.compile().as_text()
    return lowered.as_text()


def count_collectives(fn_or_text, *args, optimized: bool = False,
                      **kwargs) -> dict[str, int]:
    """Count collectives by kind.  Pass either a callable + example args, or
    an already-lowered HLO/StableHLO text."""
    if callable(fn_or_text):
        text = lowered_text(fn_or_text, *args, optimized=optimized, **kwargs)
    else:
        text = fn_or_text
    counts = {}
    for name, pats in _PATTERNS.items():
        counts[name] = sum(len(re.findall(p, text)) for p in pats)
    counts["total"] = sum(counts.values())
    return counts


# --------------------------------------------------------------- instances
#
# Per-instance parsing of *compiled* HLO: shape, payload bytes and replica
# groups of every collective — what the analysis subsystem lints against
# (``analysis.hlo_lint``).  count_collectives answers "how many"; this
# answers "of what, and across whom".

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

# "f32[16,16]{1,0}" / "bf16[8]" / "f32[]" — one array shape in HLO text.
_SHAPE_RE = re.compile(r"([a-z]+\d*(?:e\d+m\d+(?:fn)?)?)\[([\d,]*)\]")

# One collective instruction: "%name = <shape(s)> <opcode>(..." where the
# opcode is a sync collective or its async "-start" half ("-done" never
# matches: the char after the stem is "-", not "(" — same trick as
# _PATTERNS).
# A tuple shape compiled for a TPU holds parentheses of its own in its
# layouts ("bf16[48,2048]{1,0:T(8,128)(2,1)S(1)}"), so it ends at the ")"
# the opcode follows, not at the first one.
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*(?P<shape>\(.*?\)|\S+)\s+"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)(?P<start>-start)?\(")

_GROUPS_LITERAL_RE = re.compile(r"replica_groups=\{(\{[\d,{}\s]*\})\}")
# iota form: replica_groups=[4,2]<=[2,4]T(1,0) (transpose optional)
_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")


def parse_shape(s: str) -> tuple[str, tuple[int, ...]] | None:
    """One HLO array shape string -> (dtype, dims), or None if not one."""
    m = _SHAPE_RE.match(s)
    if not m:
        return None
    dt, dims = m.group(1), m.group(2)
    return dt, tuple(int(d) for d in dims.split(",")) if dims else ()


def parse_replica_groups(line: str) -> tuple[tuple[int, ...], ...] | None:
    """The replica groups of one HLO instruction line, as a tuple of
    device-id groups.  Handles both the literal ``{{0,1},{2,3}}`` form and
    the iota ``[G,S]<=[dims]T(perm)`` form (reshape-transpose of
    ``arange(n)``).  None when the line carries no parseable groups."""
    m = _GROUPS_LITERAL_RE.search(line)
    if m:
        groups = []
        for g in re.findall(r"\{([\d,\s]*)\}", m.group(1)):
            ids = tuple(int(x) for x in g.replace(" ", "").split(",") if x)
            if ids:
                groups.append(ids)
        return tuple(groups) if groups else None
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        n_groups, group_size = int(m.group(1)), int(m.group(2))
        dims = [int(d) for d in m.group(3).split(",")]
        seq = list(range(math.prod(dims)))
        if m.group(4):  # reshape to dims, transpose, then regroup
            perm = [int(p) for p in m.group(4).split(",")]
            import numpy as np
            arr = np.arange(math.prod(dims)).reshape(dims).transpose(perm)
            seq = list(arr.reshape(-1))
        return tuple(
            tuple(int(i) for i in seq[g * group_size:(g + 1) * group_size])
            for g in range(n_groups))
    return None


# --------------------------------------------------------------- shardings
#
# Entry-param/output sharding annotations of *compiled* HLO: what the
# rule-based analyzer (``analysis.rules``) lints against.  A compiled
# entry parameter line looks like
#
#   %param.1 = f32[2,16,4]{2,1,0} parameter(1),
#       sharding={devices=[1,1,2,4]<=[4,2]T(1,0) last_tile_dim_replicate},
#       metadata={op_name="p['layers']['wq']"}
#
# and the V1 literal form spells the device list out:
#   sharding={devices=[2,4]0,1,2,3,4,5,6,7}
#
# The analyzer compares *tile factor per dimension* (how many ways each
# dim is split), which both forms carry in the leading dims vector —
# device order is the replica-group lint's job, not this one's.

# the {...} payload of one sharding= attribute
_SHARDING_ATTR_RE = re.compile(r"sharding=\{([^{}]*(?:\{[^{}]*\}[^{}]*)*)\}")
# V1/V2 tile dims: devices=[2,4]... — the dims vector is common to both
_SHARDING_DEVICES_RE = re.compile(r"devices=\[([\d,]+)\]")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_PARAM_NO_RE = re.compile(r"parameter\((\d+)\)")


@dataclass(frozen=True)
class ShardingAnnotation:
    """One parsed ``sharding={...}`` attribute (array, not tuple)."""
    raw: str
    replicated: bool = False
    maximal: bool = False                  # {maximal device=k}
    tile_dims: tuple[int, ...] = ()        # tile factor per array dim
    last_tile_dim_replicate: bool = False

    def tiles(self, ndim: int) -> tuple[int, ...]:
        """Tile factor per array dimension, normalized to ``ndim``
        entries: replicated/maximal -> all 1s; a trailing
        last_tile_dim_replicate (or subgroup manual) dim is dropped."""
        if self.replicated or self.maximal:
            return (1,) * ndim
        dims = self.tile_dims
        if len(dims) > ndim:          # replicate/manual subgroup tail
            dims = dims[:ndim]
        return tuple(dims) + (1,) * (ndim - len(dims))


def parse_sharding(text: str) -> ShardingAnnotation | None:
    """Parse the first ``sharding={...}`` attribute on one HLO line (or a
    bare ``{...}`` payload).  Returns None when the line carries none.
    Tuple shardings (``{{...}, {...}}``) should be split by the caller
    (see :func:`entry_output_shardings`)."""
    m = _SHARDING_ATTR_RE.search(text)
    payload = m.group(1) if m else None
    if payload is None:
        if text.lstrip().startswith("{") or "devices=" in text \
                or "replicated" in text or "maximal" in text:
            payload = text.strip().strip("{}")
        else:
            return None
    payload = payload.strip()
    if payload.startswith("replicated"):
        return ShardingAnnotation(raw=payload, replicated=True)
    if payload.startswith("maximal"):
        return ShardingAnnotation(raw=payload, maximal=True)
    dm = _SHARDING_DEVICES_RE.search(payload)
    if not dm:
        return None
    dims = tuple(int(d) for d in dm.group(1).split(","))
    return ShardingAnnotation(
        raw=payload, tile_dims=dims,
        last_tile_dim_replicate="last_tile_dim_replicate" in payload)


@dataclass(frozen=True)
class EntryParamSharding:
    """One entry-computation parameter of a compiled module."""
    index: int
    dtype: str = ""
    dims: tuple[int, ...] = ()             # LOCAL (per-shard) dims in SPMD
    sharding: ShardingAnnotation | None = None
    op_name: str = ""                      # jax keypath, e.g. "p['embed']"
    line: str = field(default="", compare=False)


def _entry_lines(text: str):
    """The instruction lines of the ENTRY computation only — nested
    computations (scan bodies, fusions) carry parameters too."""
    inside = False
    for raw in text.splitlines():
        if raw.startswith("ENTRY"):
            inside = True
            continue
        if inside:
            if raw.strip() == "}":
                return
            yield raw


def entry_parameter_shardings(text: str) -> list[EntryParamSharding]:
    """Every ``parameter(i)`` of the ENTRY computation with its parsed
    sharding annotation (None when the compiler printed none), sorted by
    parameter index — which is the flatten order of the jitted callable's
    arguments, so rule-derived specs join positionally."""
    out = []
    for raw in _entry_lines(text):
        if "parameter(" not in raw:
            continue
        pm = _PARAM_NO_RE.search(raw)
        if not pm:
            continue
        shape = parse_shape(raw.split("=", 1)[1].strip()) \
            if "=" in raw else None
        nm = _OP_NAME_RE.search(raw)
        out.append(EntryParamSharding(
            index=int(pm.group(1)),
            dtype=shape[0] if shape else "",
            dims=shape[1] if shape else (),
            sharding=parse_sharding(raw),
            op_name=nm.group(1) if nm else "",
            line=raw.strip()))
    return sorted(out, key=lambda p: p.index)


def entry_output_shardings(text: str) -> list[ShardingAnnotation | None]:
    """The ROOT tuple's per-element sharding annotations (flatten order
    of the jitted callable's outputs), or ``[]`` when the compiled entry
    root carries no sharding attribute — output lint is best-effort."""
    for raw in _entry_lines(text):
        if not raw.lstrip().startswith("ROOT"):
            continue
        m = _SHARDING_ATTR_RE.search(raw)
        if not m:
            return []
        payload = m.group(1)
        parts = re.findall(r"\{[^{}]*\}", payload)
        if not parts:                      # single-array root
            ann = parse_sharding(raw)
            return [ann] if ann else []
        return [parse_sharding(p) for p in parts]
    return []


@dataclass(frozen=True)
class CollectiveInstance:
    """One collective instruction parsed out of compiled HLO text."""
    kind: str                                   # "all_reduce", ... (as in
    #                                             count_collectives keys)
    shapes: tuple[tuple[int, ...], ...] = ()    # output array dims
    dtypes: tuple[str, ...] = ()
    bytes: int = 0                              # summed output payload
    replica_groups: tuple[tuple[int, ...], ...] | None = None
    is_async_start: bool = False
    line: str = field(default="", compare=False)
    # instruction name ("all-reduce.1") — profiler trace events carry the
    # same name, so this is the join key of telemetry.ledger
    name: str = ""


def collective_instances(text: str) -> list[CollectiveInstance]:
    """Every collective in compiled HLO text, with shapes + replica groups.

    Async pairs are counted once (the ``-start`` op carries the info; the
    ``-done`` op never matches).  Works on post-XLA ``compile().as_text()``
    output; StableHLO callers should keep using ``count_collectives``."""
    out = []
    for raw in text.splitlines():
        m = _INSTR_RE.match(raw)
        if not m:
            continue
        shapes, dtypes, nbytes = [], [], 0
        for sm in _SHAPE_RE.finditer(m.group("shape")):
            dt = sm.group(1)
            dims = tuple(int(d) for d in sm.group(2).split(",")) \
                if sm.group(2) else ()
            shapes.append(dims)
            dtypes.append(dt)
            nbytes += math.prod(dims) * _DTYPE_BYTES.get(dt, 4)
        out.append(CollectiveInstance(
            kind=m.group("op").replace("-", "_"),
            shapes=tuple(shapes), dtypes=tuple(dtypes), bytes=nbytes,
            replica_groups=parse_replica_groups(raw),
            is_async_start=bool(m.group("start")), line=raw.strip(),
            name=m.group("name")))
    return out
