"""What every cell shares: reading ``BENCHMARK.json`` and the cell's data
files, finding the metric readers, guarding the platform, and printing the
one result line.

A cell is one entry of ``BENCHMARK.json`` ``workloads``: a configuration
(``configs/<config>.json``) under a traffic mix (``workloads/<traffic>.json``).
Nothing here names a cell, a configuration, a mix, a metric or a model's
block: they are found by the names in ``BENCHMARK.json`` and in the data
files, so a later PR adds files and entries and edits no file that is
there.

What depends on the model's block is found by the configuration file's
``"architecture": "<name>"``:

``reference/<name>.py``, the plain float32 reference, handed to the runner.
A runner lists what it calls in ``REFERENCE_EXPORTS``:

* ``serve``: ``logits_at(params, ids, positions, fields, block)`` ->
  ``(P, V)`` float32 logits at ``positions`` (P,) of the sequence ``ids``
  (S,), each against its whole causal context, queries taken ``block`` rows
  at a time; ``ids`` may be padded at the end.
* ``train``: ``loss(params, ids, labels, fields, block=None)`` -> the mean
  next-token cross-entropy of one sequence (S,), float32;
  ``group_sumsq(grads)`` and ``group_norms(grads)`` -> a dict of float32
  scalars per parameter group of a gradient tree shaped like ``params``,
  whose group names are the keys of the configuration's
  ``check.grad_norm_rel``.

``params`` is the program's own tree (``init_params`` of
``TransformerConfig(**fields)``), ``fields`` the configuration's ``fields``
as run.  A reference imports nothing of the program.

``counts/<name>.py``, the operations and bytes the block's algorithms
require, from ``fields`` alone; the readers reach it as ``ctx.counts``:
``param_count(fields)``, ``model_flops_per_token(fields, seq_len)``,
``kv_bytes_per_token(fields, itemsize=2)``,
``decode_step_bytes(fields, valid_kv_tokens, itemsize=2)``,
``attention_kernel_flops(fields, seq_len, n_seqs)``,
``attention_kernel_bytes(fields, seq_len, n_seqs, itemsize=2)``,
``proj_mlp_weight_count(fields)``.  A reader lists what it calls in
``COUNTS``; a module needs only what the readers of its cells list, and a
cell none of whose readers counts needs no module (``cell_counts``).  The
peaks (``peaks.json``) and ``roofline_seconds`` are shared by every
architecture.

A missing module or function is a ``BenchmarkError`` that names the file.

Importing this module touches neither JAX nor the program under test.
"""

from __future__ import annotations

import copy
import importlib.util
import json
import math
import os
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
#: everything a run leaves behind goes here (git-ignored, inside the checkout)
OUT = ROOT / ".bench_out"

EXIT_NO_DEVICE = 2


class BenchmarkError(Exception):
    """The benchmark's own files are wrong or incomplete."""


@dataclass
class Metric:
    """One reader: a module with ``UNIT``, ``RUNNERS`` and ``read(ctx)``;
    per-layer readers also carry ``LAYER`` and ``MOVES``."""
    name: str
    kind: str                    # "end_to_end" | "per_layer"
    entry: dict                  # its BENCHMARK.json entry
    module: object

    @property
    def unit(self) -> str:
        return self.module.UNIT

    @property
    def moves(self) -> str | None:
        return getattr(self.module, "MOVES", None)

    def applies(self, cell: "Cell", e2e_names: set[str]) -> bool:
        if cell.runner not in self.module.RUNNERS:
            return False
        only = self.entry.get("workloads")
        if only is not None and cell.name not in only:
            return False
        # a per-layer metric is reported only where the metric it moves is
        return self.kind == "end_to_end" or self.moves in e2e_names


@dataclass
class Cell:
    name: str
    chips: int
    why: str
    config_name: str
    traffic_name: str
    config: dict                 # configs/<config>.json
    traffic: dict                # workloads/<traffic>.json
    end_to_end: list[Metric] = field(default_factory=list)
    per_layer: list[Metric] = field(default_factory=list)

    @property
    def runner(self) -> str:
        return self.config["runner"]

    @property
    def architecture(self) -> str:
        return self.config["architecture"]

    @property
    def check(self) -> dict:
        return self.tolerances()

    def tolerances(self, rehearse: bool = False) -> dict:
        """The tolerances ``correct`` is held to: the configuration's
        ``check`` with the traffic file's ``check`` merged over it, so a mix
        at other sizes states the bands it read there.  A rehearsal's tiny
        model has readings of its own: both files' ``rehearse.check`` merge
        over that."""
        out = deep_merge(copy.deepcopy(self.config["check"]),
                         self.traffic.get("check", {}))
        for src in (self.config, self.traffic) if rehearse else ():
            deep_merge(out, src.get("rehearse", {}).get("check", {}))
        return out


def _read_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise BenchmarkError(f"missing file {path}") from None
    except json.JSONDecodeError as e:
        raise BenchmarkError(f"{path}: {e}") from None


def deep_merge(into: dict, over: dict) -> dict:
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(into.get(k), dict):
            deep_merge(into[k], v)
        else:
            into[k] = v
    return into


def load_benchmark(root: Path = ROOT) -> dict:
    return _read_json(root / "BENCHMARK.json")


def _import_file(path: Path):
    name = f"_bench_{path.parent.name}_{path.stem}"
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod          # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def module_path(kind_dir: str, name: str, bench: Path = BENCH) -> Path:
    path = bench / kind_dir / f"{name.replace('-', '_')}.py"
    if not path.is_file():
        raise BenchmarkError(f"no {kind_dir} module for {name!r}: {path}")
    return path


def find_module(kind_dir: str, name: str, bench: Path = BENCH, needs=()):
    """``<bench>/<kind_dir>/<name>.py`` — a reader, a generator, a runner,
    a reference or a counts module, found by the name a data file gives.
    A function of ``needs`` that it lacks is a ``BenchmarkError`` naming
    the file."""
    mod = _import_file(module_path(kind_dir, name, bench))
    for need in needs:
        if not hasattr(mod, need):
            raise BenchmarkError(f"{mod.__file__} has no {need!r}, which "
                                 f"this cell's runner or readers call")
    return mod


def cell_counts(cell: "Cell", bench: Path = BENCH):
    """``counts/<architecture>.py`` with what the cell's readers list in
    their ``COUNTS``; None for a cell none of whose readers counts."""
    needs = sorted({n for m in cell.end_to_end + cell.per_layer
                    for n in getattr(m.module, "COUNTS", ())})
    return find_module("counts", cell.architecture, bench, needs) \
        if needs else None


def load_metrics(bm: dict, bench: Path = BENCH) -> dict[str, Metric]:
    """Every metric ``BENCHMARK.json`` names, with its reader module.  A
    reader's own ``UNIT``/``LAYER``/``MOVES`` must agree with the entry."""
    out: dict[str, Metric] = {}
    for kind, kind_dir in (("end_to_end", "end_to_end"),
                           ("per_layer", "layer_metrics")):
        for entry in bm.get(kind, []):
            name = entry["name"]
            if name == "setup_s":
                continue         # taken by run.py itself, on its own clock
            mod = find_module(kind_dir, name, bench)
            m = Metric(name, kind, entry, mod)
            for key, attr in (("unit", "UNIT"), ("layer", "LAYER"),
                              ("moves", "MOVES")):
                if key in entry and entry[key] != getattr(mod, attr, None):
                    raise BenchmarkError(
                        f"{name}: BENCHMARK.json says {key}={entry[key]!r},"
                        f" its reader says {getattr(mod, attr, None)!r}")
            out[name] = m
    return out


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bm = load_benchmark(root)
    bench = root / bm["paths"][0]
    rows = [w for w in bm["workloads"] if w["name"] == name]
    if not rows:
        known = ", ".join(w["name"] for w in bm["workloads"])
        raise BenchmarkError(f"no cell {name!r}; BENCHMARK.json has: {known}")
    row = rows[0]
    cfg_row = next((c for c in bm["configs"] if c["name"] == row["config"]),
                   None)
    if cfg_row is None:
        raise BenchmarkError(f"cell {name!r} names an unknown config "
                             f"{row['config']!r}")
    cell = Cell(name=name, chips=int(row["chips"]), why=row["why"],
                config_name=row["config"], traffic_name=row["traffic"],
                config=_read_json(root / cfg_row["file"]),
                traffic=_read_json(bench / "workloads"
                                   / f"{row['traffic']}.json"))
    if "architecture" not in cell.config:
        raise BenchmarkError(f"{root / cfg_row['file']} names no "
                             f"\"architecture\"")
    module_path("reference", cell.architecture, bench)
    metrics = load_metrics(bm, bench)
    cell.end_to_end = [m for m in metrics.values()
                       if m.kind == "end_to_end" and m.applies(cell, set())]
    e2e = {m.name for m in cell.end_to_end}
    cell.per_layer = [m for m in metrics.values()
                      if m.kind == "per_layer" and m.applies(cell, e2e)]
    cell_counts(cell, bench)     # missing counts fail here, not after a run
    return cell


def list_cells(root: Path = ROOT) -> list[dict]:
    """What ``run.py --list`` prints: every cell with the metrics it would
    report.  Reads data files and reader modules only."""
    bm = load_benchmark(root)
    out = []
    for row in bm["workloads"]:
        cell = load_cell(row["name"], root)
        out.append({"cell": cell.name, "config": cell.config_name,
                    "traffic": cell.traffic_name, "chips": cell.chips,
                    "runner": cell.runner,
                    "architecture": cell.architecture,
                    "end_to_end": ["setup_s"] + [m.name
                                                 for m in cell.end_to_end],
                    "per_layer": [m.name for m in cell.per_layer]})
    return out


# ------------------------------------------------------------ the platform

def prepare_platform(chips: int, rehearse_cpu: bool) -> None:
    """Before JAX is imported.  A rehearsal forces the CPU with as many
    virtual devices as the cell has chips; a real run forces nothing, so
    JAX takes the accelerator or fails."""
    if "jax" in sys.modules:
        raise BenchmarkError("prepare_platform() must run before jax is "
                             "imported")
    # libtpu would otherwise log under /tmp/tpu_logs, a fixed path outside
    # the checkout that two sides of a comparison would share
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        flags.append(f"--xla_force_host_platform_device_count={chips}")
        os.environ["XLA_FLAGS"] = " ".join(flags)


def assert_accelerator(chips: int) -> dict | None:
    """The device block of the result line, or None when this machine has
    no TPU or fewer chips than the cell asks for (the caller then exits 2
    and prints no result).  ``chip_smoke.py``'s assertion, copied."""
    import jax
    try:
        backend = jax.default_backend()
        devs = jax.devices()
    except RuntimeError as e:
        print(f"[bench] no accelerator: {e}", file=sys.stderr)
        return None
    if backend != "tpu" or len(devs) < chips:
        print(f"[bench] need {chips} TPU chip(s); JAX reports backend "
              f"{backend!r} with {len(devs)} device(s)", file=sys.stderr)
        return None
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def model_config(fields: dict):
    """The program's ``TransformerConfig`` from a configuration file's
    ``fields``; ``dtype`` is the one field that is not plain data."""
    import jax.numpy as jnp
    from distributed_training_sandbox_tpu.models import transformer as T
    kw = dict(fields)
    kw["dtype"] = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        kw.get("dtype", "bfloat16")]
    return T.TransformerConfig(**kw)


def load_peaks(device_kind: str, bench: Path = BENCH) -> dict:
    """The published peaks of ``device_kind``.  A device that is not in
    the table is an error, never a default."""
    table = _read_json(bench / "peaks.json")
    if device_kind not in table:
        raise BenchmarkError(
            f"no peaks for device_kind {device_kind!r} in peaks.json "
            f"(has: {', '.join(k for k in table if not k.startswith('_'))})")
    return table[device_kind]


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip, as the allocator reports."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


# ------------------------------------------------------ arithmetic, results

def roofline_seconds(flops: float, nbytes: float,
                     peaks: dict) -> tuple[float, str]:
    """The least time one chip could take in bf16 for these operations and
    bytes, from the published peaks, and which peak bounds it."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between order
    statistics.  An empty sample has no percentile: NaN."""
    import numpy as np
    xs = np.asarray(list(values), np.float64)
    return float(np.percentile(xs, q)) if xs.size else math.nan


class Phases:
    """Where the set-up time went: ``mark(label)`` closes a phase; the run
    prints them to stderr, so that a later PR sees what to shorten."""

    def __init__(self, t0: float):
        self.last = t0
        self.spent: list[tuple[str, float]] = []

    def mark(self, label: str) -> None:
        now = time.perf_counter()
        self.spent.append((label, now - self.last))
        self.last = now

    def __str__(self) -> str:
        return ", ".join(f"{k} {v:.1f}s" for k, v in self.spent)


class CompileWatch:
    """Counts XLA compilations by when they ended, so a run can say how
    many fell inside its measured window (there must be none)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.ends: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.ends.append(time.perf_counter())

    def inside(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.ends if t0 <= t <= t1)


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: dict, device: dict,
                breakdown: dict | None = None,
                compared: dict | None = None) -> str:
    """The contract's one JSON object.  ``metrics`` maps name to
    ``(value, unit)``; a value that is not a finite number is a fault of
    the run, not a result.  ``compared`` is what ``correct`` was decided
    from, ``{name: [number, limit]}`` (a band: ``[number, least, most]``),
    under a key of its own that comes last."""
    out = {}
    for name, (value, unit) in metrics.items():
        if value is None or not math.isfinite(float(value)):
            raise BenchmarkError(f"metric {name} is {value!r}")
        out[name] = {"value": float(value), "unit": unit}
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": out, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    if compared is not None:
        line["compared"] = compared
    return json.dumps(line)


# ------------------------------------------------------------------ tracing

WINDOW_SPAN = "bench/window"


def start_trace() -> str:
    """Start JAX's profiler into a fresh fixed directory under ``OUT``.
    Host spans are ``jax.profiler.TraceAnnotation``s, so they share the
    device events' clock; the Python call tracer stays off (it slows the
    host and floods the trace)."""
    import shutil
    import jax
    trace_dir = OUT / "trace"
    shutil.rmtree(trace_dir, ignore_errors=True)
    trace_dir.mkdir(parents=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    return str(trace_dir)


def spans(trace: bool):
    """``span(name)``: a profiler annotation in a traced run, so that the
    host span shares the device events' clock; nothing otherwise."""
    import contextlib
    if not trace:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


@dataclass
class Context:
    """What a metric reader may read."""
    cell: Cell
    fields: dict                 # the configuration's fields as run
    counters: dict               # the runner's counts and host-clock times
    peaks: dict | None           # peaks.json row of this device
    counts: object | None = None  # cell_counts(cell): the block's arithmetic
    trace: object | None = None  # reduce_trace.ReducedTrace, traced run only

    @property
    def chips(self) -> int:
        return self.cell.chips


def read_metrics(metrics: list[Metric], ctx: Context) -> dict:
    """name -> (value, unit) for every reader that found something to
    read; a reader that returns None is left out of the line."""
    out = {}
    for m in metrics:
        value = m.module.read(ctx)
        if value is not None:
            out[m.name] = (value, m.unit)
    return out
