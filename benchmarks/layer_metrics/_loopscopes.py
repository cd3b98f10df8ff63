"""Shared by the readers of the name the looped dense block opens where a
pass ends: ``utils/profiling.LOOP_SUBSCOPES`` (``loop_gate``, beneath
``sample``, in a decode step and in a prefill chunk).  ``_scopes`` books
such an op to ``sample``, which also holds the head and the argmax; this
helper reads the same trace once more and books an op to the innermost of
THESE names, with ``_attnscopes``' rules (its ``reduce`` under this
module's names, as ``_ccascopes`` does).  A program that opens no such
scope, as every program before this block, gives an empty table and the
readers return nothing.
"""

from __future__ import annotations

from benchmarks import harness, reduce_trace as R
from benchmarks.layer_metrics import _attnscopes as AS
from benchmarks.layer_metrics import _scopes as S
from benchmarks.layer_metrics import _subscopes as SS

#: ``distributed_training_sandbox_tpu/utils/profiling.py``
#: ``LOOP_SUBSCOPES``, copied; a test holds the copy to the original
LOOP_SUBSCOPES = ("loop_gate",)

_NAMES = frozenset(LOOP_SUBSCOPES)


def innermost(path: str | None) -> str | None:
    """The innermost LOOP_SUBSCOPES name anywhere in an op's scope path."""
    for word in reversed(SS._WORD.findall(path or "")):
        if word in _NAMES:
            return word
    return None


def reduce(raw: S.ScopedRaw, window: tuple[float, float]) -> dict:
    """``{(program, subscope): self ns}`` inside ``window``: the rule of
    ``_attnscopes.reduce`` over the ops under these names, each renamed to
    the path ``_attnscopes`` books (self time is taken among the ops under
    the name alone)."""
    renamed = S.ScopedRaw(devices={
        dev: {**lines, "ops": [
            (n, s, d, AS.ATTENTION_SUBSCOPES[0] if innermost(path) else "")
            for n, s, d, path in lines["ops"]]}
        for dev, lines in raw.devices.items()})
    return {(prog, LOOP_SUBSCOPES[0]): ns
            for (prog, _), ns in AS.reduce(renamed, window).items()}


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
