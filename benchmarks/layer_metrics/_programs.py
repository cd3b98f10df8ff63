"""Shared by the readers of the engine's two programs.  Both are
``jit__unknown(...)`` in a trace, so a program is found by its launch count
(``reduce_trace.alias_modules`` against what the runner counted), and its
time is the device time of its launches on the module line: what the chip
spent in it, whatever the host was waiting for meanwhile.  The engine's own
``prefill_s`` / ``decode_s`` are host-clock times around asynchronous
dispatch, and book a chunk's device time to whichever call syncs next."""
from benchmarks.reduce_trace import alias_modules


def device_seconds_per_launch(ctx, label: str):
    """Traced device seconds per launch of the program the runner counted
    under ``label`` (``decode`` / ``prefill``), or None when the run was
    not traced or no program's launch count fits."""
    if ctx.trace is None:
        return None
    named = alias_modules(ctx.trace, ctx.counters["program_launches"])
    mods = [m for m, lab in named.items() if lab == label]
    if not mods:
        return None
    launches, ns = ctx.trace.chips[0].modules[mods[0]]
    return ns / 1e9 / launches
