"""Run manifest: the startup facts of one training run.

Captured once before the step loop — everything a later reader needs to
know *what* was run in order to trust the numbers in ``steps.jsonl``:
strategy, the full ``TrainConfig``, mesh geometry, device kind/count,
process topology, jax/jaxlib versions, git sha, and the compile-time HLO
collective counts (``ops.hlo.count_collectives``) of the step function —
the choreography fingerprint that lets the report CLI show "N
all-reduces/step" next to step time.

The startup fields are immutable.  When the run owned a profiler,
``TelemetryRun.finalize`` rewrites the file exactly once to append the
measured-side fields: ``profile_sessions`` (the exact profiler session
dirs this run created — trace ownership, so analysis never grabs a
concurrent run's newer trace), ``ledger`` (the trace-measured
contract verdict from ``telemetry.ledger``, beside the static
``contract`` verdict it mirrors) and ``memory`` (the MemoryVerdict from
``telemetry.memledger`` — the measured-waterline third mark).
"""

from __future__ import annotations

import dataclasses
import datetime
import os
import subprocess
from dataclasses import dataclass, field
from typing import Any

MANIFEST_SCHEMA_VERSION = 1


def _git_sha() -> str | None:
    """Best-effort checkout sha; None outside a git work tree."""
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=5)
        return out.stdout.strip() or None if out.returncode == 0 else None
    except (OSError, subprocess.SubprocessError):
        return None


def _config_dict(config: Any) -> dict:
    if config is None:
        return {}
    if dataclasses.is_dataclass(config) and not isinstance(config, type):
        return dataclasses.asdict(config)
    if isinstance(config, dict):
        return dict(config)
    return {"repr": repr(config)}


@dataclass
class RunManifest:
    schema: int = MANIFEST_SCHEMA_VERSION
    run_id: str = ""
    strategy: str = ""
    model: str | None = None
    config: dict = field(default_factory=dict)
    mesh_shape: dict = field(default_factory=dict)
    mesh_axes: list = field(default_factory=list)
    device_kind: str = ""
    device_count: int = 0
    local_device_count: int = 0
    process_index: int = 0
    process_count: int = 1
    # os pid of the emitting worker — with extra["rank"] this keys the
    # cross-rank merge (fleet_timeline) back to a concrete process
    pid: int | None = None
    platform: str = ""
    jax_version: str = ""
    jaxlib_version: str | None = None
    git_sha: str | None = None
    started_utc: str = ""
    collective_counts: dict | None = None
    contract: dict | None = None
    # the partition-rule verdict (analysis.rules.rules_manifest_verdict):
    # rule hygiene over the live trees + committed NamedSharding specs
    # vs the rule-derived ones, recorded beside the static contract mark
    rules: dict | None = None
    # restart lineage (resilience.supervisor): attempt index, restart
    # budget, resumed_from_step, the resume contract re-check, and the
    # prior segments' {run_id, start/end_step, status} records —
    # scripts/report.py stitches these into one segmented-run view
    lineage: dict | None = None
    # appended at finalize when the run owned a profiler (see module
    # docstring): session dirs this run's traces live in, and the
    # measured collective-ledger verdict beside the static contract one
    profile_sessions: list | None = None
    ledger: dict | None = None
    # the memory ledger's MemoryVerdict (telemetry.memledger): measured
    # allocator peak joined to the compiled memory_analysis() waterline
    # and, where the driver passed one, the planner prediction
    memory: dict | None = None
    extra: dict = field(default_factory=dict)

    @classmethod
    def capture(cls, strategy: str, *, run_id: str = "",
                config: Any = None, mesh=None, model: str | None = None,
                collective_counts: dict | None = None,
                contract: dict | None = None,
                rules: dict | None = None,
                lineage: dict | None = None,
                extra: dict | None = None) -> "RunManifest":
        """Snapshot the environment at step 0.  ``mesh`` is a
        ``jax.sharding.Mesh`` (or None for meshless scripts);
        ``collective_counts`` is the ``count_collectives`` dict the
        scripts already compute for their startup print; ``contract``
        is the ``analysis.ContractVerdict.to_dict()`` of checking those
        counts against the strategy's choreography contract."""
        import jax
        import jaxlib
        dev = jax.devices()[0]
        return cls(
            run_id=run_id,
            strategy=strategy,
            model=model,
            config=_config_dict(config),
            mesh_shape=dict(mesh.shape) if mesh is not None else {},
            mesh_axes=list(mesh.axis_names) if mesh is not None else [],
            device_kind=dev.device_kind,
            device_count=jax.device_count(),
            local_device_count=len(jax.local_devices()),
            process_index=jax.process_index(),
            process_count=jax.process_count(),
            pid=os.getpid(),
            platform=dev.platform,
            jax_version=jax.__version__,
            jaxlib_version=jaxlib.__version__,
            git_sha=_git_sha(),
            started_utc=datetime.datetime.now(
                datetime.timezone.utc).isoformat(timespec="seconds"),
            collective_counts=collective_counts,
            contract=contract,
            rules=rules,
            lineage=dict(lineage) if lineage else None,
            extra=dict(extra or {}),
        )

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)
