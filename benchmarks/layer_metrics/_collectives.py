"""Shared by the readers of the program's own collective ledger: bytes on
the wire a step, exposed time by kind and bus bandwidth by kind, all from
``distributed_training_sandbox_tpu.utils.trace_analysis.collective_events``
(the program's reader of its own trace: one record an executed collective
with its kind, nccl-tests message bytes, in-flight and exposed time).

The helper finds the run's ``.xplane.pb`` as ``_scopes.py`` does, reads it
ONCE a run over the window ``reduce_trace`` reduced, and sums per chip.
Accounting is nccl-tests': a collective's bus bytes are its message times
``ops.busbench.bus_factor(kind, n)`` ((n - 1)/n for an all-gather and a
reduce-scatter, 2(n - 1)/n for an all-reduce, 1 for a permute); a
bandwidth is bus bytes over IN-FLIGHT time (an asynchronous collective's
start to its done, so a gather that XLA paces under the matmuls it hides
behind reads the pace, not the link), in GB/s beside the published
``ici_bits_per_s / 8`` of ``peaks.json``.  A reading over that peak is a
fault of the accounting and raises.  The worst chip, as
``collective_exposed_pct`` reads: most bytes, most exposed time, least
bandwidth.

A program without the reader (a parent older than it), a run on one chip
and a run that was not traced give None, and the line leaves the metric
out.

    python benchmarks/layer_metrics/_collectives.py <trace.xplane.pb> [steps]
"""

from __future__ import annotations

import sys
import time
from pathlib import Path

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks import harness, reduce_trace as R  # noqa: E402

_EVENTS: dict[str, dict] = {}


def events(ctx) -> dict | None:
    """``{plane: [CollectiveEvent]}`` of the traced run's window, read once
    a process; None where there is nothing to read."""
    if ctx.trace is None or ctx.chips < 2:
        return None
    try:
        from distributed_training_sandbox_tpu.utils.trace_analysis import (
            collective_events)
    except ImportError:
        return None
    path = R.find_xplane(str(harness.OUT / "trace"))
    if path not in _EVENTS:
        t0 = time.perf_counter()
        _EVENTS[path] = collective_events(path, group=ctx.chips,
                                          window=ctx.trace.window)
        print(f"[bench] collective ledger read in "
              f"{time.perf_counter() - t0:.2f} s\n"
              + report(_EVENTS[path], ctx.chips, ctx.counters["steps"]),
              file=sys.stderr)
    return _EVENTS[path]


def bus_bytes(ev, n: int) -> float:
    from distributed_training_sandbox_tpu.ops.busbench import bus_factor
    return ev.bytes * bus_factor(ev.kind, n)


def per_chip(ctx, value, kind: str | None = None) -> list[float] | None:
    """``value(events of one chip, of ``kind`` when given)`` per chip;
    None when the run has no such event."""
    planes = events(ctx)
    if not planes:
        return None
    picked = [[e for e in evs if kind in (None, e.kind)]
              for evs in planes.values()]
    if not any(picked) or any(e.bytes is None for evs in picked for e in evs):
        return None
    return [value(evs) for evs in picked]


def bytes_per_step_gb(ctx) -> float | None:
    chips = per_chip(ctx, lambda evs: sum(bus_bytes(e, ctx.chips)
                                          for e in evs))
    return max(chips) / ctx.counters["steps"] / 1e9 if chips else None


def exposed_ms_per_step(ctx, kind: str) -> float | None:
    chips = per_chip(ctx, lambda evs: sum(e.exposed_ns for e in evs), kind)
    return max(chips) / ctx.counters["steps"] / 1e6 if chips else None


def busbw_gbps(ctx, kind: str) -> float | None:
    """Bus bytes over in-flight time of ``kind``, the slowest chip; bytes
    per ns are GB/s."""
    def rate(evs):
        flight = sum(e.inflight_ns for e in evs)
        return sum(bus_bytes(e, ctx.chips) for e in evs) / flight \
            if flight else 0.0
    chips = per_chip(ctx, rate, kind)
    if not chips:
        return None
    peak = ctx.peaks["ici_bits_per_s"] / 8 / 1e9
    if max(chips) > peak:
        raise harness.BenchmarkError(
            f"{kind} bus bandwidth reads {max(chips):.1f} GB/s, over the "
            f"chip's {peak:.0f} GB/s of ICI: bytes are counted too high (a "
            f"-start and its -done both booked?) or the in-flight time "
            f"leaves part of the transfer out")
    return min(chips)


def report(planes: dict, n: int, steps: int) -> str:
    """The table by kind, scope and phase, for stderr: the busiest chip."""
    plane, evs = max(planes.items(),
                     key=lambda kv: sum(e.exposed_ns for e in kv[1]))
    rows: dict[tuple, list] = {}
    for e in evs:
        row = rows.setdefault((e.kind, e.scope or "-", e.phase),
                              [0, 0.0, 0.0, 0.0])
        row[0] += 1
        row[1] += bus_bytes(e, n) if e.bytes is not None else 0.0
        row[2] += e.inflight_ns
        row[3] += e.exposed_ns
    lines = [f"[bench] collectives of {plane}, a step of {steps}: kind, "
             f"scope, phase, instances, bus GB, in-flight ms, exposed ms, "
             f"bus GB/s"]
    for (kind, scope, phase), (cnt, nbytes, flight, exposed) in sorted(
            rows.items(), key=lambda kv: -kv[1][3]):
        lines.append(
            f"[bench]   {kind:<18} {scope:<18} {phase} {cnt / steps:7.1f} "
            f"{nbytes / steps / 1e9:8.4f} {flight / steps / 1e6:9.3f} "
            f"{exposed / steps / 1e6:8.3f} "
            f"{nbytes / flight if flight else 0.0:7.2f}")
    return "\n".join(lines)


def main(argv=None) -> int:
    from distributed_training_sandbox_tpu.utils.trace_analysis import (
        collective_events)
    args = argv or sys.argv[1:]
    planes = collective_events(args[0])
    print(report(planes, len(planes), int(args[1]) if args[1:] else 1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
