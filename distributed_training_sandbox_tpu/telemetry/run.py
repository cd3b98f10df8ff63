"""TelemetryRun: the one object a training script holds.

Glues the pieces together around the step loop:

  * captures and writes the :class:`RunManifest` at entry;
  * appends one ``schema.step_event`` per optimizer step — every rank
    emits, rank > 0 under a ``-r<rank>`` run-id suffix so
    ``scripts/fleet_timeline.py`` can merge a launch group — timing
    steps host-side and lifting rates from the ``PerformanceTracker``
    metrics dict the scripts already compute;
  * owns the live :class:`~.metrics.MetricsRegistry` (fed by the pump,
    prefetcher, checkpointer, batcher, router and heartbeats) and, when
    ``--metrics-port`` is set, its Prometheus scrape endpoint plus
    periodic ``metrics.jsonl`` snapshots;
  * owns the ``Profiler`` lifecycle — ``step()`` advances it and
    ``__exit__`` stops it on *every* path, so an exception mid-loop
    still flushes the in-flight ``jax.profiler`` trace (the reference
    scripts only called ``prof.stop()`` on the happy path and lost the
    trace on crash);
  * owns the host-phase ``SpanStream`` (``spans.jsonl``) the runtime
    pieces (pump, prefetcher, checkpointer, serving engine) record
    their wait/dispatch spans into;
  * writes ``summary.json`` at exit — aggregates plus, when profiling
    was on, the ``trace_analysis.split_from_trace`` comm/compute split
    of the profiler session this run *owns* (not "newest trace by
    mtime" — a concurrent run must not be misattributed) and the trace
    dir; a crash writes status="crashed" with the error;
  * when the script also attached its compiled HLO (:meth:`attach_hlo`),
    builds the :mod:`telemetry.ledger` CollectiveLedger from the owned
    trace — per-collective payloads and bus-GB/s in
    ``collectives.json``, with the measured contract verdict appended
    to ``manifest.json`` beside the static one;
  * when :meth:`attach_step_hlo` also captured the compiled step's
    ``memory_analysis()``, builds the :mod:`telemetry.memledger`
    MemoryLedger — attributed categories + the phase-spanned allocator
    timeline in ``memory.json``, with the MemoryVerdict stamped into
    ``manifest.json`` as the third mark beside the contract and
    collective-ledger verdicts.

Usage (the shape every scripts/ entrypoint now follows)::

    with TelemetryRun("fsdp", config=cfg, mesh=mesh, model=args.model,
                      collective_counts=counts, profiler=prof) as telem:
        for i in range(cfg.num_steps):
            ...
            metrics = tracker.step(tokens, loss=loss)
            telem.step(loss=loss, tokens=tokens, tracker_metrics=metrics)
    # telemetry + profiler both finalized here, crash or not
"""

from __future__ import annotations

import os
import statistics
import time

from ..utils.config import build_run_id, default_results_dir
from .manifest import RunManifest
from .schema import step_event
from .writer import MetricsWriter


HLO_FILENAME = "step.hlo.txt"


class TelemetryRun:
    def __init__(self, strategy: str, *, config=None, mesh=None,
                 model: str | None = None,
                 collective_counts: dict | None = None,
                 contract: dict | None = None,
                 rules: dict | None = None,
                 lineage: dict | None = None,
                 extra: dict | None = None,
                 results_dir: str | None = None,
                 run_name: str | None = None,
                 profiler=None, enabled: bool | None = None,
                 metrics_port: int | None = None,
                 metrics_snapshot_s: float = 10.0):
        import jax
        self.strategy = strategy
        self.config = config
        self.mesh = mesh
        self.model = model
        self.collective_counts = collective_counts
        self.contract = contract
        self.rules = rules
        self.lineage = lineage
        self.extra = extra
        self.profiler = profiler
        if results_dir is None:
            results_dir = getattr(config, "results_dir", None) \
                or default_results_dir()
        if run_name is None:
            run_name = getattr(config, "run_name", None)
        want = getattr(config, "telemetry", True) if enabled is None \
            else enabled
        # every rank emits its own artifacts (rank > 0 under a
        # ``-r<rank>`` run-id suffix) so scripts/fleet_timeline.py can
        # merge a launch group; DTS_PROCESS_ID wins over
        # jax.process_index() so launcher-spawned workers that never
        # initialize jax.distributed still stamp their true rank
        env_rank = os.environ.get("DTS_PROCESS_ID")
        self.rank = int(env_rank) if env_rank else jax.process_index()
        self.enabled = bool(want)
        self.results_dir = results_dir
        self.run_id = self._unique_run_id(results_dir, strategy, run_name,
                                          rank=self.rank)
        self.run_dir = os.path.join(results_dir, self.run_id) \
            if self.enabled else None
        # live metrics: registry always present while enabled (feed
        # sites are None-guarded), HTTP endpoint only on request
        if metrics_port is None:
            metrics_port = getattr(config, "metrics_port", None)
        self._metrics_port = metrics_port
        self.metrics_snapshot_s = float(metrics_snapshot_s)
        self.metrics = None
        self.metrics_server = None
        self._t_metrics_snapshot: float | None = None
        self._metrics_snapshots = 0
        if self.enabled:
            from .metrics import MetricsRegistry
            self.metrics = MetricsRegistry()
        self.writer: MetricsWriter | None = None
        self.manifest: RunManifest | None = None
        self._step_idx = 0
        self._losses: list[float] = []
        self._step_times: list[float] = []
        self._last_tracker_metrics: dict | None = None
        self._tokens_total = 0
        self._t_prev: float | None = None
        self._finalized = False
        # deferred (device-array) losses: events buffered here until the
        # pump's next sync point resolves them (see flush())
        self._deferred: list[tuple[dict, object]] = []
        # set by StepPump.close(); lands in summary.json
        self.host_sync_count: int | None = None
        self.host_sync_breakdown: dict | None = None
        # host-phase span stream (spans.jsonl), created at start();
        # None when telemetry is off — call sites guard via maybe_span
        self.spans = None
        # compiled HLO of the step program (attach_hlo), joined against
        # the owned trace at finalize to build the collective ledger
        self._hlo_text: str | None = None
        # compiled-step memory accounting (attach_step_hlo): the
        # memory_analysis() breakdown, eager tree-walk bytes per named
        # arg category (computed BEFORE donation invalidates the
        # buffers), per-path param attribution, and the driver's
        # planner/serving prediction — joined at finalize into the
        # memory ledger (memory.json)
        self._memory_analysis: dict | None = None
        self._mem_trees_bytes: dict | None = None
        self._mem_param_paths: dict | None = None
        self._mem_prediction: dict | None = None

    @staticmethod
    def _unique_run_id(results_dir: str, strategy: str,
                       run_name: str | None, rank: int = 0) -> str:
        label = strategy if not run_name else f"{strategy}-{run_name}"
        rid = build_run_id(label)
        if rank:
            # rank-suffixed so N ranks of one launch group land as N
            # sibling run dirs (merged by scripts/fleet_timeline.py)
            rid = f"{rid}-r{rank}"
        # second-resolution timestamps collide when two runs start in the
        # same second (the test suite does exactly that)
        n, base = 2, rid
        while os.path.exists(os.path.join(results_dir, rid)):
            rid = f"{base}-{n}"
            n += 1
        return rid

    # ---- lifecycle ------------------------------------------------------
    def start(self) -> "TelemetryRun":
        if self.enabled:
            extra = dict(self.extra or {})
            extra.setdefault("rank", self.rank)
            group = os.environ.get("DTS_LAUNCH_GROUP")
            if group:
                # launcher-stamped group id: fleet_timeline groups the
                # per-rank run dirs of one `dts-launch run` by this key
                extra.setdefault("launch_group", group)
            coord = os.environ.get("DTS_COORDINATOR")
            if coord:
                # the launcher-chosen coordinator address:port — the
                # fleet-timeline join can tell two groups apart even
                # when their launch ids collide, and a port-rotation
                # retry is visible as a changed port across attempts
                extra.setdefault("coordinator", coord)
            self.manifest = RunManifest.capture(
                self.strategy, run_id=self.run_id, config=self.config,
                mesh=self.mesh, model=self.model,
                collective_counts=self.collective_counts,
                contract=self.contract,
                rules=self.rules,
                lineage=self.lineage,
                extra=extra)
            self.writer = MetricsWriter(self.run_dir)
            self.writer.write_manifest(self.manifest)
            from .spans import SpanStream
            self.spans = SpanStream(self.run_dir)
            # phase-spanned allocator timeline: every host span the
            # stream appends also samples the shared device-memory
            # sampler under that span's phase (memledger.PHASES)
            from .memledger import get_sampler
            self.spans.sampler = get_sampler()
            if self._metrics_port is not None:
                from .metrics import MetricsServer
                self.metrics_server = MetricsServer(
                    self.metrics, port=int(self._metrics_port)).start()
                self._t_metrics_snapshot = time.perf_counter()
        self._t_prev = time.perf_counter()
        return self

    def attach_hlo(self, compiled_text: str) -> None:
        """Hand over the step program's ``compile().as_text()`` so
        finalize can join the profiler trace against it (the collective
        ledger needs instruction names + payload shapes).  Scripts call
        this only when profiling is on — lowering+compiling purely for
        the text would otherwise double compile cost.  The text is also
        filed as ``step.hlo.txt``: what the device was asked to run
        (which kernels, which collectives) stays readable after the
        process is gone."""
        self._hlo_text = compiled_text
        if self.writer is not None:
            self.writer.write_text(HLO_FILENAME, compiled_text)

    def attach_step_hlo(self, jitted, *args, trees=None,
                        prediction=None) -> None:
        """Driver-facing form of :meth:`attach_hlo`: AOT-lower ``jitted``
        at ``args`` and attach the compiled text.  ``args`` MUST be the
        exact arrays the hot loop passes (same shapes, dtypes AND
        shardings) — a differently-sharded example would compile a
        different program whose instruction names don't match the traced
        one, and the ledger join would report every site unmeasured.
        No-op unless this run owns an *enabled* profiler (no trace, no
        join — don't pay the extra compile); never raises.

        The same compile also feeds the memory ledger: its
        ``memory_analysis()`` breakdown is captured, and ``trees`` — a
        ``{category: pytree}`` dict of the named argument state
        (defaulting to ``{params, opt_state, batch}`` from the first
        three positional args, the universal train-step signature) — is
        tree-walked into per-category bytes EAGERLY, because donation
        invalidates these buffers the moment the hot loop runs.
        ``prediction`` (a WaterlinePrediction-shaped dict, optional)
        records the driver's analytic/serving waterline for the
        measured-vs-predicted join at finalize."""
        prof = self.profiler
        if not self.enabled or self._hlo_text is not None \
                or prof is None or not getattr(prof, "enabled", False):
            return
        try:
            compiled = jitted.lower(*args).compile()
            self.attach_hlo(compiled.as_text())
        except Exception as e:   # best-effort: telemetry must not crash
            print(f"[telemetry] WARNING: could not attach compiled HLO "
                  f"for the collective ledger: {type(e).__name__}: {e}")
            return
        try:
            ma = compiled.memory_analysis()
            if ma is not None:
                self._memory_analysis = {
                    "argument_bytes": int(ma.argument_size_in_bytes),
                    "output_bytes": int(ma.output_size_in_bytes),
                    "temp_bytes": int(ma.temp_size_in_bytes),
                    "alias_bytes": int(ma.alias_size_in_bytes),
                }
            if trees is None and len(args) >= 3:
                trees = {"params": args[0], "opt_state": args[1],
                         "batch": args[2]}
            if trees:
                from ..utils.memory import tree_size_bytes
                from .memledger import param_path_bytes
                self._mem_trees_bytes = {
                    k: tree_size_bytes(v) for k, v in trees.items()}
                if "params" in trees:
                    self._mem_param_paths = param_path_bytes(
                        trees["params"])
            if prediction is not None:
                self._mem_prediction = prediction.to_dict() \
                    if hasattr(prediction, "to_dict") else dict(prediction)
        except Exception as e:   # best-effort: telemetry must not crash
            print(f"[telemetry] WARNING: could not attribute step memory "
                  f"for the memory ledger: {type(e).__name__}: {e}")

    def __enter__(self) -> "TelemetryRun":
        return self.start()

    def __exit__(self, exc_type, exc, tb):
        # profiler first: flush the in-flight trace whatever happened
        if self.profiler is not None:
            self.profiler.stop()
        if exc_type is not None:
            self.finalize(status="crashed",
                          error=f"{exc_type.__name__}: {exc}")
        else:
            self.finalize()
        return False

    # ---- per-step -------------------------------------------------------
    def step(self, *, loss=None, tokens: int | None = None,
             tracker_metrics: dict | None = None, **extra) -> None:
        """Record one optimizer step.  Also advances the owned profiler,
        so the loop needs no separate ``prof.step()`` call.

        ``loss`` may be a host float (written through immediately, the
        classic path) or a *device array* still in flight — then the
        event is buffered with a null loss and resolved at the next
        :meth:`flush` (the async pump's sync points), so the JSONL
        schema is unchanged and rows stay in step order."""
        now = time.perf_counter()
        dt = now - self._t_prev if self._t_prev is not None else None
        self._t_prev = now
        if self.profiler is not None:
            self.profiler.step()
        tm = tracker_metrics or {}
        step_time = tm.get("last_step_time_s") or dt
        extra.setdefault("rank", self.rank)
        if self.metrics is not None:
            self.metrics.inc("steps_total")
            if tokens:
                self.metrics.inc("tokens_total", int(tokens))
            if step_time is not None:
                self.metrics.set("last_step_time_s", float(step_time))
            self._maybe_snapshot_metrics(now)
        deferred = loss is not None and hasattr(loss, "block_until_ready")
        if step_time is not None:
            self._step_times.append(float(step_time))
        if tokens:
            self._tokens_total += int(tokens)
        if tm:
            self._last_tracker_metrics = tm
        idx = self._step_idx
        self._step_idx += 1
        if deferred:
            ev = step_event(idx, loss=None, tokens=tokens,
                            step_time_s=step_time,
                            tracker_metrics=tracker_metrics, **extra)
            self._deferred.append((ev, loss))
            return
        if self._deferred:       # keep steps.jsonl in step order
            self.flush()
        if loss is not None:
            self._losses.append(float(loss))
        if self.writer is not None:
            self.writer.append_step(step_event(
                idx, loss=loss, tokens=tokens, step_time_s=step_time,
                tracker_metrics=tracker_metrics, **extra))

    def _maybe_snapshot_metrics(self, now: float) -> None:
        """Append a timestamped line to ``metrics.jsonl`` every
        ``metrics_snapshot_s`` while the endpoint is live (snapshots and
        endpoint are one feature: runs that never asked for live
        metrics keep their exact artifact set)."""
        if self.metrics_server is None or self.run_dir is None \
                or self._t_metrics_snapshot is None:
            return
        if now - self._t_metrics_snapshot < self.metrics_snapshot_s:
            return
        self._t_metrics_snapshot = now
        try:
            self.metrics.write_snapshot(
                os.path.join(self.run_dir, "metrics.jsonl"))
            self._metrics_snapshots += 1
        except OSError:
            pass

    def flush(self, up_to: int | None = None) -> None:
        """Resolve buffered deferred-loss events (oldest first; all of
        them, or the first ``up_to``) and hand them to the writer.  The
        caller — the pump at a sync point, or finalize — is responsible
        for the losses being (near-)ready; resolution of a poisoned
        array degrades to a null loss rather than raising."""
        n = len(self._deferred) if up_to is None \
            else min(up_to, len(self._deferred))
        for _ in range(n):
            ev, arr = self._deferred.pop(0)
            try:
                from ..utils.mesh import local_scalar
                lf = local_scalar(arr)
            except Exception:   # crash path: keep the original exception
                lf = None
            if lf is not None:
                ev["loss"] = lf
                self._losses.append(lf)
            if self.writer is not None:
                self.writer.append_step(ev)
        if self.writer is not None:
            self.writer.flush()

    # ---- end-of-run -----------------------------------------------------
    def _aggregates(self) -> dict:
        out: dict = {
            "steps_recorded": self._step_idx,
            "total_tokens": self._tokens_total,
        }
        if self._losses:
            out["first_loss"] = self._losses[0]
            out["final_loss"] = self._losses[-1]
            out["avg_loss"] = sum(self._losses) / len(self._losses)
        if self._step_times:
            # median over the post-compile tail: step 0 carries the jit
            times = self._step_times[1:] or self._step_times
            out["step_time_ms"] = statistics.median(times) * 1e3
            out["step_time_ms_mean"] = sum(times) / len(times) * 1e3
        tm = self._last_tracker_metrics or {}
        for k in ("tokens_per_second", "steps_per_second",
                  "tflops_per_device", "peak_memory_gb"):
            if tm.get(k) is not None:
                out[k] = tm[k]
        return out

    def finalize(self, status: str = "completed", error: str | None = None,
                 **extra) -> dict | None:
        """Write ``summary.json``.  Idempotent: a crash path overwrites a
        not-yet-written summary only; explicit double calls are no-ops."""
        if self._finalized:
            return None
        self._finalized = True
        try:
            self.flush()     # resolve any still-deferred losses
        except Exception:
            pass
        if not self.enabled or self.writer is None:
            return None
        summary: dict = {
            "run_id": self.run_id,
            "strategy": self.strategy,
            "model": self.model,
            "status": status,
        }
        if error:
            summary["error"] = error
        cfg = self.manifest.config if self.manifest else {}
        for k in ("sequence_length", "batch_size", "num_steps",
                  "precision", "seed"):
            if k in cfg:
                summary[k] = cfg[k]
        summary.update(self._aggregates())
        if self.host_sync_count is not None:
            # the pump's instrumented blocking events (policy barriers +
            # backpressure waits) — the async-dispatch acceptance metric
            summary["host_sync_count"] = self.host_sync_count
            summary["host_sync_breakdown"] = self.host_sync_breakdown
        summary.update(extra)
        # post-run profiling hook: comm/compute split + collective
        # ledger from the trace session the owned Profiler just flushed
        # (falling back to newest-under-trace_dir only when the profiler
        # predates session ownership)
        prof = self.profiler
        if prof is not None and getattr(prof, "enabled", False):
            summary["trace_dir"] = prof.trace_dir
            owned = list(getattr(prof, "owned_sessions", None) or [])
            session = owned[-1] if owned else None
            if owned:
                summary["profile_sessions"] = owned
            try:
                from ..utils.trace_analysis import split_from_trace
                sp = split_from_trace(prof.trace_dir, session=session)
            except Exception:   # trace parsing must never fail the run
                sp = None
            if sp is not None:
                summary["comm_split"] = {
                    "comm_us": sp.comm_us,
                    "compute_us": sp.compute_us,
                    "other_us": sp.other_us,
                    "comm_fraction": sp.comm_fraction,
                    "overlap_us": sp.overlap_us,
                    "overlap_fraction": sp.overlap_fraction,
                    "trace_file": sp.trace_file,
                }
            ledger_verdict = None
            if self._hlo_text is not None:
                try:
                    ledger_verdict = self._build_ledger(session)
                except Exception:   # ledger must never fail the run
                    ledger_verdict = None
            if ledger_verdict is not None:
                summary["ledger"] = ledger_verdict
            mem_verdict = None
            if self._memory_analysis is not None:
                try:
                    mem_verdict = self._build_memory()
                except Exception:   # memory ledger must never fail the run
                    mem_verdict = None
            if mem_verdict is not None:
                summary["memory"] = mem_verdict
            if self.manifest is not None and (owned or ledger_verdict
                                              or mem_verdict):
                # the one sanctioned manifest rewrite (see
                # telemetry.manifest): append the measured-side facts
                self.manifest.profile_sessions = owned or None
                self.manifest.ledger = ledger_verdict
                self.manifest.memory = mem_verdict
                self.writer.write_manifest(self.manifest)
        if self.spans is not None:
            self.spans.close()
            if self.spans.spans_written:
                summary["spans_recorded"] = self.spans.spans_written
        if self.metrics is not None and self.metrics:
            # final counter values — the live endpoint's last scrape and
            # this block must agree (pinned by test_obsplane)
            summary["metrics"] = self.metrics.snapshot()
        if self.metrics_server is not None:
            try:
                self.metrics.write_snapshot(
                    os.path.join(self.run_dir, "metrics.jsonl"))
            except OSError:
                pass
            self.metrics_server.stop()
            self.metrics_server = None
        self.writer.write_summary(summary)
        self.writer.close()
        return summary

    def _build_memory(self) -> dict | None:
        """Build + file the memory ledger (``memory.json``); returns the
        MemoryVerdict block stamped into summary/manifest beside the
        contract and collective-ledger verdicts, or None when the attach
        captured no ``memory_analysis()``."""
        if self._memory_analysis is None:
            return None
        from .memledger import (MEMORY_FILENAME, build_memory_ledger,
                                get_sampler, join_prediction)
        capacity = None
        cfg = self.manifest.config if self.manifest else {}
        if isinstance(cfg, dict) and cfg.get("hbm_budget_gb"):
            capacity = float(cfg["hbm_budget_gb"])
        led = build_memory_ledger(
            self._memory_analysis, self._mem_trees_bytes,
            self._hlo_text or "", sampler=get_sampler(),
            param_paths=self._mem_param_paths, capacity_gb=capacity)
        verdict = join_prediction(led, self._mem_prediction,
                                  strategy=self.strategy)
        self.writer.write_json(MEMORY_FILENAME, led.to_dict())
        return verdict

    def _build_ledger(self, session: str | None) -> dict | None:
        """Build + file the collective ledger; returns the compact
        verdict block that lands in summary/manifest, or None when no
        trace was found."""
        from .ledger import join_contract, ledger_from_trace
        axis_sizes = dict(self.mesh.shape) if self.mesh is not None \
            else dict((self.manifest.mesh_shape or {})
                      if self.manifest else {})
        led = ledger_from_trace(self.profiler.trace_dir, self._hlo_text,
                                axis_sizes, session=session)
        if led is None:
            return None
        join = None
        if self.contract and isinstance(self.contract, dict) \
                and self.contract.get("expected"):
            join = join_contract(led, self.contract["expected"],
                                 strategy=self.strategy)
        self.writer.write_json("collectives.json", led.to_dict())
        totals = led.totals()
        out = {
            "measured_sites": totals["measured_sites"],
            "unmeasured_sites": totals["unmeasured_sites"],
            "unmatched_events": totals["unmatched_events"],
            "busbw_gbps": totals["busbw_gbps"],
        }
        if join is not None:
            out["ok"] = join["ok"]
            out["violations"] = join["violations"]
        return out
