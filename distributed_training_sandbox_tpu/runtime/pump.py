"""Bounded async dispatch with a declared host-sync policy.

The synchronous loop this replaces paid two host round-trips per step
(``jax.block_until_ready(loss)`` + ``float(loss)``), serializing host
and device.  The pump instead lets up to ``max_in_flight`` dispatched
steps retire their losses as *device arrays* and only blocks the host
at three policy points:

  * profile-schedule boundaries (so ``jax.profiler`` traces bound
    exactly the intended steps — checked via
    ``Profiler.pending_transition``);
  * every ``sync_every`` steps (the ``--sync-every`` flag);
  * loop exit (``close()`` / the ``with`` exit, crash included).

A wait at such a point is a ``pump/<reason>`` span; the reads that follow
it (the losses retired since the last point, one blocking device-to-host
read each) are one ``pump/resolve`` span that says how many (``reads``).

Plus a fourth, non-policy wait: when ``max_in_flight`` losses are
pending, the oldest is retired before dispatching further (backpressure,
so an unbounded host can't race arbitrarily far ahead of the device).
Every blocking event is instrumented: ``host_sync_count`` and its
per-reason breakdown land in the run's ``summary.json``.

Losses are resolved to floats at sync points and fed, in step order, to
the ``TelemetryRun`` (which buffered the deferred events — the JSONL
schema is unchanged), to ``PerformanceTracker.record_loss`` (so
``avg_loss`` survives async mode), and to the per-step ``log`` callbacks
the drivers pass for their console prints.

``mode="sync"`` reproduces the old strictly synchronous loop through
the same code path — the A/B lever the smoke test uses.
"""

from __future__ import annotations

from collections import deque


def _to_float(x) -> float:
    from ..utils.mesh import local_scalar
    return local_scalar(x)


class StepPump:
    """Drive one training loop's loss retirement and sync policy.

    Usage (the shape every strategy driver now follows)::

        with TelemetryRun(...) as telem:
            with StepPump(telem=telem, tracker=tracker,
                          mode=cfg.dispatch, sync_every=cfg.sync_every,
                          max_in_flight=cfg.max_in_flight) as pump:
                for i, batch in zip(range(cfg.num_steps), prefetcher):
                    params, opt, loss = step(params, opt, batch)
                    pump.emit(loss, tokens=..., log=maybe_print)
            metrics = pump.metrics   # final tracker metrics, losses resolved
    """

    def __init__(self, *, telem=None, tracker=None, mode: str = "async",
                 sync_every: int = 10, max_in_flight: int = 16,
                 profiler=None, watchdog=None):
        if mode not in ("async", "sync"):
            raise ValueError(f"dispatch mode must be async|sync, got {mode!r}")
        if max_in_flight < 1:
            raise ValueError(f"max_in_flight must be >= 1, got {max_in_flight}")
        self.telem = telem
        self.tracker = tracker
        self.mode = mode
        self.sync_every = max(int(sync_every), 0)
        self.max_in_flight = int(max_in_flight)
        # collective watchdog (resilience.elastic.Watchdog): every
        # blocking sync point below routes through it so a hung
        # collective becomes a diagnosable StepTimeoutError with the
        # in-flight step index attached, never a silent deadlock
        self.watchdog = watchdog
        self.profiler = profiler if profiler is not None \
            else getattr(telem, "profiler", None)
        self._pending: deque = deque()   # (step_idx, device loss, log cb)
        self._emitted = 0
        self._closed = False
        self.resolved: list[tuple[int, float]] = []  # (step_idx, loss)
        self.sync_breakdown: dict[str, int] = {}
        self.metrics: dict | None = None

    # ---- accounting ------------------------------------------------------
    @property
    def host_sync_count(self) -> int:
        return sum(self.sync_breakdown.values())

    @property
    def losses(self) -> list[float]:
        """Resolved losses in step order (complete after ``close()``)."""
        return [l for _, l in self.resolved]

    def _count(self, reason: str) -> None:
        self.sync_breakdown[reason] = self.sync_breakdown.get(reason, 0) + 1
        from ..telemetry.metrics import maybe_inc
        maybe_inc(getattr(self.telem, "metrics", None),
                  "pump_host_sync_total", reason=reason)

    def _block(self, arr, step: int | None = None,
               reason: str = "sync") -> None:
        """One blocking wait at a sync point, watchdog-guarded and
        recorded as a ``pump/<reason>`` host span when the telemetry run
        carries a span stream (the timeline evidence of where the host
        actually stalls)."""
        import jax
        from ..telemetry.spans import maybe_span
        # the reason set is closed (per_step/profile_boundary/sync_every/
        # throttle/drain/exit), so the span-name cardinality is bounded
        with maybe_span(getattr(self.telem, "spans", None),  # span-ok
                        f"pump/{reason}", cat="pump", step=step):
            if self.watchdog is not None:
                self.watchdog.block(jax.block_until_ready, arr, step=step)
            else:
                jax.block_until_ready(arr)

    # ---- resolution ------------------------------------------------------
    def _resolve_one(self, idx: int, arr, log) -> float | None:
        try:
            lf = _to_float(arr)
        except Exception:   # crash path: a poisoned array must not mask
            return None     # the original loop exception
        self.resolved.append((idx, lf))
        if self.tracker is not None:
            self.tracker.record_loss(lf)
        if log is not None:
            log(lf)
        return lf

    def _drain(self) -> None:
        """Resolve every pending loss (oldest first) and flush the
        telemetry events that were deferred on them."""
        if not self._pending:
            return
        self._block(self._pending[-1][1], step=self._pending[-1][0],
                    reason="drain")
        while self._pending:
            self._resolve_one(*self._pending.popleft())
        if self.telem is not None:
            self.telem.flush()

    # ---- the per-step call ----------------------------------------------
    def emit(self, loss, *, tokens: int | None = None, log=None,
             **extra) -> bool:
        """Record one dispatched step whose loss is ``loss`` (a device
        array).  ``log``, if given, is called with the resolved float at
        sync time — drivers put their console prints there.

        Returns True when this step was a full sync point (everything
        up to and including this loss resolved) — the signal the
        resilience checkpointer rides so async saves land on the
        existing host-sync schedule instead of adding barriers."""
        if self._closed:
            raise RuntimeError("emit() after close()")
        i = self._emitted
        self._emitted += 1
        metrics = None
        if self.tracker is not None:
            metrics = self.tracker.step(tokens or 0)
        boundary = (self.profiler is not None
                    and getattr(self.profiler, "enabled", False)
                    and self.profiler.pending_transition())
        if self.mode == "sync" or boundary or (
                self.sync_every and (i + 1) % self.sync_every == 0):
            reason = ("per_step" if self.mode == "sync"
                      else "profile_boundary" if boundary
                      else "sync_every")
            self._block(loss, step=i, reason=reason)
            # the wait is over; every retired loss is now read back, one
            # blocking device-to-host read each, which a trace would
            # otherwise show as the caller's own time
            from ..telemetry.spans import maybe_span
            with maybe_span(getattr(self.telem, "spans", None),
                            "pump/resolve", cat="pump", step=i,
                            reads=len(self._pending) + 1):
                self._drain()
                lf = self._resolve_one(i, loss, log)
            self._count(reason)
            if self.telem is not None:
                self.telem.step(loss=lf, tokens=tokens,
                                tracker_metrics=metrics, **extra)
            return True
        else:
            self._pending.append((i, loss, log))
            if self.telem is not None:
                # deferred: TelemetryRun buffers the event and resolves
                # the device-array loss at flush time
                self.telem.step(loss=loss, tokens=tokens,
                                tracker_metrics=metrics, **extra)
            if len(self._pending) > self.max_in_flight:
                idx0, arr0, log0 = self._pending.popleft()
                self._block(arr0, step=idx0, reason="throttle")
                self._resolve_one(idx0, arr0, log0)
                if self.telem is not None:
                    self.telem.flush(up_to=1)
                self._count("throttle")
            return False

    # ---- lifecycle -------------------------------------------------------
    def close(self) -> None:
        """Drain in-flight losses (one final barrier when any are
        pending), snapshot final tracker metrics, and report the sync
        accounting into the owning TelemetryRun's summary."""
        if self._closed:
            return
        self._closed = True
        if self._pending:
            try:
                self._drain()
            finally:
                self._count("exit")
        if self.tracker is not None:
            self.metrics = self.tracker.metrics(sample_memory=True)
        if self.telem is not None:
            self.telem.host_sync_count = self.host_sync_count
            self.telem.host_sync_breakdown = dict(self.sync_breakdown)

    def __enter__(self) -> "StepPump":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False
