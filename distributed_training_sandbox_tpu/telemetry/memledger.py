"""Memory ledger — measured HBM attribution joined to planner waterlines.

The measured twin of ``memory_plan``'s WaterlinePrediction, built the way
``telemetry/ledger.py`` is the measured twin of the collective contract:

* :func:`attribute_categories` parses the compiled step's
  ``memory_analysis()`` breakdown into attributed categories — params,
  opt-state, batch (tree-walked eagerly at attach time, BEFORE donation
  invalidates the buffers), collective scratch (payload bytes of every
  ``ops.hlo.collective_instances`` site in the compiled text),
  remat-policy saved activations (``checkpoint_name`` metadata, where the
  compiled text carries it) and the residual activation workspace —
  keyed by named param paths under the same name normalization the
  collective ledger applies to trace events (leading ``%`` and scope
  prefixes stripped).
* :class:`MemorySampler` is the ONE process-wide poll site over
  ``utils.memory.device_memory_stats`` — ``utils.tracker`` and
  ``utils.memory.all_devices_memory_gb`` both route through
  :func:`get_sampler`, and the span stream feeds it a phase per host
  span so ``memory.json`` records per-phase live-allocator peaks for
  prefetch/dispatch/sync/checkpoint/prefill/decode.
* :func:`join_prediction` produces the MemoryVerdict: measured peak vs
  the compiled ``memory_analysis()`` waterline within a pinned band,
  plus (when the driver recorded one) the analytic/serving prediction
  with per-category residuals — stamped into ``manifest.json`` as the
  third mark beside the static contract and collective-ledger verdicts.

Substrate honesty: CPU-simulated devices expose no allocator stats, so
the measured peak degrades to the compile-side accounting
(args + out + temp − alias) with ``measured_source="accounted"`` —
the attribution and the join still run; real HBM numbers arrive with
``measured_source="allocator"`` on a TPU slice.
"""

from __future__ import annotations

import json
import math
import os
import re
import threading
from dataclasses import dataclass, field
from typing import Any

from ..ops.hlo import _DTYPE_BYTES, _SHAPE_RE, collective_instances
from ..utils.memory import GB, device_memory_stats

MEMORY_FILENAME = "memory.json"
MEMORY_SCHEMA_VERSION = 1

# the phase vocabulary of the live-allocator timeline — every host span
# the SpanStream emits maps into one of these (or none)
PHASES = ("prefetch", "dispatch", "sync", "checkpoint", "prefill", "decode")

# measured/predicted ratio bands by prediction source.  The
# memory_analysis band is tight — on the accounted fallback the ratio is
# exactly 1, and a real allocator peak should sit within fragmentation
# slack of the compiler's plan.  Analytic and serving-accounting bands
# mirror the CPU-mesh calibration pinned by tests/test_memory_plan.py
# (the tight ~10% analytic calibration is against TPU verdicts only).
PREDICTION_BANDS = {
    "memory_analysis": (0.5, 2.0),
    "analytic": (0.2, 5.0),
    "serve_accounting": (0.2, 5.0),
}
DEFAULT_BAND = (0.2, 5.0)


# ------------------------------------------------------------- sampler

class MemorySampler:
    """The single shared device-memory poll site.

    Thread-safe: the span stream samples from whatever thread emits the
    span (prefetcher, checkpoint writer, pump).  Tracks the global and
    per-phase peak of ``max(bytes_in_use, peak_bytes_in_use)`` in GB.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self.samples = 0
        self.peak_gb = 0.0
        self.phase_peaks_gb: dict[str, float] = {}
        self.last_stats: dict[str, int] = {}

    def sample(self, phase: str | None = None) -> dict[str, int]:
        """Poll device 0's allocator, fold into the (phase) peaks, and
        return the raw stats dict (zeros on backends without stats)."""
        stats = device_memory_stats()
        hi = max(stats["bytes_in_use"], stats["peak_bytes_in_use"]) / GB
        with self._lock:
            self.samples += 1
            self.last_stats = stats
            if hi > self.peak_gb:
                self.peak_gb = hi
            if phase is not None:
                self.phase_peaks_gb[phase] = max(
                    self.phase_peaks_gb.get(phase, 0.0), hi)
        return stats

    def all_devices_gb(self) -> dict[str, dict[str, float]]:
        """Per-device current/peak GB — the one loop over
        ``jax.local_devices()`` that ``utils.memory.all_devices_memory_gb``
        delegates to."""
        import jax
        out = {}
        for d in jax.local_devices():
            s = device_memory_stats(d)
            out[str(d.id)] = {
                "current_gb": s["bytes_in_use"] / GB,
                "peak_gb": s["peak_bytes_in_use"] / GB,
            }
        return out

    def snapshot(self) -> dict[str, Any]:
        with self._lock:
            return {"samples": self.samples, "peak_gb": self.peak_gb,
                    "phase_peaks_gb": dict(self.phase_peaks_gb)}

    def reset(self) -> None:
        with self._lock:
            self.samples = 0
            self.peak_gb = 0.0
            self.phase_peaks_gb = {}
            self.last_stats = {}


_SAMPLER: MemorySampler | None = None
_SAMPLER_LOCK = threading.Lock()


def get_sampler() -> MemorySampler:
    """The process-wide shared sampler (identity pinned by test)."""
    global _SAMPLER
    with _SAMPLER_LOCK:
        if _SAMPLER is None:
            _SAMPLER = MemorySampler()
        return _SAMPLER


def reset_sampler() -> None:
    """Drop accumulated peaks — test isolation hook."""
    get_sampler().reset()


def phase_for_span(name: str, cat: str | None = None) -> str | None:
    """Map a host span (name, cat) onto the phase vocabulary, or None
    for spans outside the memory timeline (telemetry internals)."""
    name = name or ""
    cat = cat or ""
    if cat == "prefetch" or name.startswith("prefetch"):
        return "prefetch"
    if cat == "checkpoint" or name.startswith("checkpoint"):
        return "checkpoint"
    if name.startswith("serve/"):
        # the engine's round: one allocator read per device sync, not
        # one per span
        return {"serve/prefill_sync": "prefill",
                "serve/burst_sync": "decode"}.get(name)
    if "prefill" in name:
        return "prefill"
    if "decode" in name:
        return "decode"
    if cat == "pump" or name.startswith("pump"):
        if any(t in name for t in ("sync", "drain", "throttle")):
            return "sync"
        return "dispatch"
    return None


# --------------------------------------------------------- attribution

def _normalize_name(s: str) -> str:
    """The collective ledger's trace-event name normalization
    (``utils.trace_analysis.normalize_event_name``): leading ``%`` and
    scope prefixes stripped — applied to param paths so the same key
    joins trees, HLO instructions and trace events."""
    return s.lstrip("%").rsplit("/", 1)[-1]


def param_path_bytes(tree: Any, top: int = 32) -> dict[str, int]:
    """Per-named-path byte attribution of a param tree (dot-joined pytree
    path, normalized like HLO instruction names), largest ``top`` paths."""
    import jax
    out: dict[str, int] = {}
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    for path, leaf in leaves:
        if not hasattr(leaf, "nbytes"):
            continue
        parts = []
        for p in path:
            for attr in ("key", "idx", "name"):
                if hasattr(p, attr):
                    parts.append(str(getattr(p, attr)))
                    break
            else:
                parts.append(str(p))
        name = _normalize_name(".".join(parts))
        out[name] = out.get(name, 0) + int(leaf.nbytes)
    ranked = sorted(out.items(), key=lambda kv: (-kv[1], kv[0]))
    return dict(ranked[:top])


_RESULT_RE = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*"
                        r"(?P<shape>\([^)]*\)|\S+)\s")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_SAVE_NAME_RE = re.compile(r"checkpoint_name\[\s*name\s*=\s*([\w\-./]+)")


def _shape_bytes(tok: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(tok):
        dims = tuple(int(d) for d in m.group(2).split(",")) \
            if m.group(2) else ()
        total += math.prod(dims) * _DTYPE_BYTES.get(m.group(1), 4)
    return total


def saved_activation_bytes(text: str) -> tuple[int, list[str]]:
    """Bytes (and save names) of buffers the remat policy pinned across
    the boundary, where the compiled text carries ``checkpoint_name``
    metadata.  Compilers that drop the metadata yield ``(0, [])`` — the
    'where available' half of the attribution contract."""
    total, names = 0, []
    for raw in text.splitlines():
        op = _OP_NAME_RE.search(raw)
        if not op:
            continue
        save = _SAVE_NAME_RE.search(op.group(1))
        if not save:
            continue
        res = _RESULT_RE.match(raw)
        if not res:
            continue
        total += _shape_bytes(res.group("shape"))
        name = _normalize_name(save.group(1))
        if name not in names:
            names.append(name)
    return total, names


def attribute_categories(mem: dict[str, int],
                         trees_bytes: dict[str, int] | None,
                         hlo_text: str = "") -> tuple[dict[str, int],
                                                      list[str]]:
    """Split the compiled step's ``memory_analysis()`` breakdown into
    attributed byte categories.

    ``mem``: ``{argument_bytes, output_bytes, temp_bytes, alias_bytes}``.
    ``trees_bytes``: eager tree-walk bytes per named argument category
    (params / opt_state / batch / kv_pool ...) — these partition the
    argument buffers; whatever they don't cover lands in
    ``unattributed_args``.  Temps split into collective scratch (summed
    ``collective_instances`` payloads), policy-saved activations and the
    residual ``activations_workspace``.
    """
    args_b = int(mem.get("argument_bytes", 0))
    out_b = int(mem.get("output_bytes", 0))
    temp_b = int(mem.get("temp_bytes", 0))
    scratch = 0
    saved, saved_names = 0, []
    if hlo_text:
        scratch = sum(i.bytes for i in collective_instances(hlo_text))
        saved, saved_names = saved_activation_bytes(hlo_text)
    cats = {k: int(v) for k, v in (trees_bytes or {}).items()}
    cats["unattributed_args"] = max(args_b - sum(cats.values()), 0)
    cats["out"] = out_b
    cats["collective_scratch"] = min(scratch, temp_b)
    # scratch and saved together never exceed temps — the residual
    # workspace stays a true partition remainder, never negative
    cats["saved_activations"] = min(saved,
                                    temp_b - cats["collective_scratch"])
    cats["activations_workspace"] = (
        temp_b - cats["collective_scratch"] - cats["saved_activations"])
    return cats, saved_names


# --------------------------------------------------------------- ledger

@dataclass
class MemoryLedger:
    """Attributed compile-side accounting + the live allocator timeline
    of one run — what ``memory.json`` serializes."""
    categories_gb: dict[str, float]
    param_paths_gb: dict[str, float]
    compiled: dict[str, float]          # argument/output/temp/alias GB
    #                                     + waterline_gb
    phase_peaks_gb: dict[str, float]
    samples: int
    measured_peak_gb: float
    measured_source: str                # "allocator" | "accounted"
    capacity_gb: float | None = None
    saved_names: list[str] = field(default_factory=list)
    prediction_join: dict | None = None

    def to_dict(self) -> dict:
        return {
            "schema": MEMORY_SCHEMA_VERSION,
            "categories_gb": {k: round(v, 9)
                              for k, v in self.categories_gb.items()},
            "param_paths_gb": {k: round(v, 9)
                               for k, v in self.param_paths_gb.items()},
            "compiled": {k: round(v, 9) for k, v in self.compiled.items()},
            "phase_peaks_gb": dict(self.phase_peaks_gb),
            "samples": self.samples,
            "measured_peak_gb": round(self.measured_peak_gb, 9),
            "measured_source": self.measured_source,
            "capacity_gb": self.capacity_gb,
            "saved_names": list(self.saved_names),
            "prediction_join": self.prediction_join,
        }

    def write(self, run_dir: str) -> str:
        path = os.path.join(run_dir, MEMORY_FILENAME)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.to_dict(), f, indent=1, sort_keys=True)
        os.replace(tmp, path)
        return path


def build_memory_ledger(mem: dict[str, int],
                        trees_bytes: dict[str, int] | None = None,
                        hlo_text: str = "", *,
                        sampler: MemorySampler | None = None,
                        param_paths: dict[str, int] | None = None,
                        capacity_gb: float | None = None) -> MemoryLedger:
    """Join compile-side accounting with the sampler's live timeline.

    The measured peak prefers the allocator (nonzero peak from any
    sample); on stat-less backends it falls back to the accounted
    waterline so the verdict stays meaningful on the CPU tier.
    """
    args_b = int(mem.get("argument_bytes", 0))
    out_b = int(mem.get("output_bytes", 0))
    temp_b = int(mem.get("temp_bytes", 0))
    alias_b = int(mem.get("alias_bytes", 0))
    waterline_gb = (args_b + out_b + temp_b - alias_b) / GB
    cats, saved_names = attribute_categories(mem, trees_bytes, hlo_text)
    snap = sampler.snapshot() if sampler is not None \
        else {"samples": 0, "peak_gb": 0.0, "phase_peaks_gb": {}}
    alloc_peak = float(snap.get("peak_gb", 0.0))
    if alloc_peak > 0.0:
        measured, source = alloc_peak, "allocator"
    else:
        measured, source = waterline_gb, "accounted"
    return MemoryLedger(
        categories_gb={k: v / GB for k, v in cats.items()},
        param_paths_gb={k: v / GB for k, v in (param_paths or {}).items()},
        compiled={"argument_gb": args_b / GB, "output_gb": out_b / GB,
                  "temp_gb": temp_b / GB, "alias_gb": alias_b / GB,
                  "waterline_gb": waterline_gb},
        phase_peaks_gb=dict(snap.get("phase_peaks_gb", {})),
        samples=int(snap.get("samples", 0)),
        measured_peak_gb=measured,
        measured_source=source,
        capacity_gb=capacity_gb,
        saved_names=saved_names,
    )


# ----------------------------------------------------- prediction join

# analytic-component → measured-category aliases (the predictor calls
# the optimizer term "opt"; the attributed tree category is "opt_state")
_COMPONENT_ALIASES = {"opt": "opt_state"}


def join_prediction(ledger: MemoryLedger, prediction: Any = None,
                    strategy: str = "") -> dict:
    """The MemoryVerdict: the measured twin of WaterlinePrediction.judge.

    Always judges the measured peak against the compiled
    ``memory_analysis()`` waterline (the pinned acceptance band); when
    the driver recorded a planner/serving prediction it is judged too,
    within its source's band, with per-category residuals (measured GB −
    predicted component GB over the categories both sides name).  The
    verdict is ``ok`` only when every judged band holds.
    """
    violations: list[str] = []
    measured = ledger.measured_peak_gb
    compiled_gb = ledger.compiled.get("waterline_gb", 0.0)
    lo, hi = PREDICTION_BANDS["memory_analysis"]
    ratio_c = measured / compiled_gb if compiled_gb > 0 else float("inf")
    ok = compiled_gb > 0 and lo < ratio_c < hi
    if not ok:
        violations.append(
            f"measured {measured:.4f} GB vs compiled {compiled_gb:.4f} GB: "
            f"ratio {ratio_c:.3f} outside ({lo}, {hi})")
    verdict: dict[str, Any] = {
        "strategy": strategy,
        "measured_gb": round(measured, 6),
        "measured_source": ledger.measured_source,
        "compiled_gb": round(compiled_gb, 6),
        "compiled_ratio": round(ratio_c, 6) if compiled_gb > 0 else None,
        "compiled_band": [lo, hi],
        "residuals": {},
    }
    if prediction is not None:
        pd = prediction.to_dict() if hasattr(prediction, "to_dict") \
            else dict(prediction)
        pred_gb = pd.get("predicted_gb")
        source = pd.get("source") or "analytic"
        if pred_gb:
            plo, phi = PREDICTION_BANDS.get(source, DEFAULT_BAND)
            ratio_p = measured / float(pred_gb)
            verdict.update(predicted_gb=round(float(pred_gb), 6),
                           predicted_source=source,
                           predicted_ratio=round(ratio_p, 6),
                           predicted_band=[plo, phi])
            if not plo < ratio_p < phi:
                ok = False
                violations.append(
                    f"measured {measured:.4f} GB vs predicted "
                    f"{float(pred_gb):.4f} GB ({source}): ratio "
                    f"{ratio_p:.3f} outside ({plo}, {phi})")
            comps = pd.get("components") or {}
            for k, v in comps.items():
                mk = _COMPONENT_ALIASES.get(k, k)
                if mk in ledger.categories_gb:
                    verdict["residuals"][mk] = round(
                        ledger.categories_gb[mk] - float(v), 6)
    verdict["ok"] = ok
    verdict["violations"] = violations
    ledger.prediction_join = verdict
    return verdict


# ------------------------------------------------ artifacts & the gate

def load_memory_dict(run_dir: str) -> dict | None:
    """``memory.json`` of a run dir as a dict, or None when absent or
    unreadable (mirrors ``ledger.load_ledger_dict``)."""
    path = os.path.join(run_dir, MEMORY_FILENAME)
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return None


def memory_aggregates(doc: dict) -> dict[str, float]:
    """Flatten a memory.json dict into the gate's key → GB form: the
    measured peak plus one ``cat/<name>`` key per attributed category."""
    out = {"peak": float(doc.get("measured_peak_gb") or 0.0)}
    for k, v in (doc.get("categories_gb") or {}).items():
        out[f"cat/{k}"] = float(v)
    return out


def check_memory_regressions(cur: dict[str, float],
                             base: dict[str, float],
                             max_growth_pct: float = 20.0,
                             label: str = "",
                             base_label: str = "") -> list[dict]:
    """Direction-aware memory gate: GROWTH is the bad direction (the
    mirror image of the bandwidth gate, where a drop regresses).  Keys
    present on only one side are skipped, not errors."""
    recs = []
    for key in sorted(cur):
        gb, base_gb = cur[key], base.get(key)
        if not base_gb:
            continue
        delta_pct = (gb / base_gb - 1.0) * 100.0
        recs.append({
            "run_id": label, "baseline": base_label, "key": key,
            "gb": gb, "baseline_gb": base_gb,
            "delta_pct": delta_pct, "max_growth_pct": max_growth_pct,
            "regressed": delta_pct > max_growth_pct,
        })
    return recs
