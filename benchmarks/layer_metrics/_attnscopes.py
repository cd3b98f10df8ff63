"""Shared by the readers of the name the hybrid with expert layers opens
round its full-attention layers' paged attention:
``utils/profiling.ATTENTION_SUBSCOPES`` (``attn_paged``, beneath
``attn_core``, in a decode step and in a prefill chunk).  ``_scopes`` books
such an op to ``attn_core``, which in that block also holds the linear
layers' step or scan; this helper reads the same trace once more and books
it to the innermost of THESE names, with ``_scopes``' window and
``_subscopes``' rule for self time and for which program an op ran in.  A
program that opens no such scope, as every program before this block,
gives an empty table and the readers return nothing.
"""

from __future__ import annotations

import bisect

from benchmarks import harness, reduce_trace as R
from benchmarks.layer_metrics import _scopes as S
from benchmarks.layer_metrics import _subscopes as SS

#: ``distributed_training_sandbox_tpu/utils/profiling.py``
#: ``ATTENTION_SUBSCOPES``, copied; a test holds the copy to the original
ATTENTION_SUBSCOPES = ("attn_paged",)

_NAMES = frozenset(ATTENTION_SUBSCOPES)


def innermost(path: str | None) -> str | None:
    """The innermost ATTENTION_SUBSCOPES name anywhere in an op's scope
    path."""
    for word in reversed(SS._WORD.findall(path or "")):
        if word in _NAMES:
            return word
    return None


def reduce(raw: S.ScopedRaw, window: tuple[float, float]) -> dict:
    """``{(program, subscope): self ns}`` inside ``window``, mean over the
    chips; ops under none of the names are left out."""
    lo, hi = window
    out: dict[tuple[str, str], float] = {}
    for lines in raw.devices.values():
        leaves = [(innermost(path) or S.NO_SCOPE, max(s, lo),
                   min(s + d, hi) - max(s, lo))
                  for n, s, d, path in lines["ops"]
                  if s + d > lo and s < hi and not R.CONTAINERS.match(n)]
        launches = sorted((s, s + d, R.module_group(n))
                          for n, s, d in lines.get("modules", []))
        starts = [m[0] for m in launches]
        for scope, s, _, own in R._self_times(leaves):
            if scope == S.NO_SCOPE:
                continue
            i = bisect.bisect_right(starts, s) - 1
            prog = launches[i][2] if i >= 0 and s < launches[i][1] else "?"
            out[(prog, scope)] = out.get((prog, scope), 0.0) \
                + own / len(raw.devices)
    return out


_TABLES: dict[str, dict] = {}


def subscope_ms_per_launch(ctx, names, label: str) -> float | None:
    """Self ms under the names ``names`` per launch of the program the
    runner counted under ``label``; None when the run was not traced or
    nothing ran under them."""
    tab = S.table(ctx)
    mods = [m for m, lab in (S.programs(ctx) if tab else {}).items()
            if lab == label]
    if not mods:
        return None
    path = R.find_xplane(str(harness.OUT / "trace"))
    if path not in _TABLES:
        _TABLES[path] = reduce(S.load(path), tab.window)
    ns = sum(v for (prog, scope), v in _TABLES[path].items()
             if prog == mods[0] and scope in names)
    launches = ctx.trace.chips[0].modules[mods[0]][0]
    return ns / 1e6 / launches if ns else None
