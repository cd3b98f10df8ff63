"""Fleet observability plane suite — THE acceptance for cross-rank
trace aggregation: N per-rank run dirs of one launch group merge into
ONE Perfetto timeline with a named track per rank, and the straggler
report names the slowed rank per pump sync site (pinned in-process with
controlled clock offsets, and over a real ``dts-launch run --nprocs 2``
group with an injected ``slow@N:ms`` in the slow leg); a fleet
``kill_replica`` run yields request swimlanes where the replayed
request's spans share one ``trace_id`` across both replicas and the
TTFT decomposition counts the replay once and sums to the measured
TTFT; scraping the live metrics endpoint mid-run returns Prometheus
text whose final counters match ``summary.json``; and the run registry
folds >= 3 runs' ledger aggregates into a cost model that round-trips
through its loader.  Satellites: the bounded-error clock-anchor
sidecar (lazy — span-free runs keep their exact artifact set), rank
stamping + ``-rN`` run-id suffixing, the span-name-cardinality lint
(red/green + swept trees stay clean), export_timeline event ordering,
and steps-schema back-compat for the optional tracing fields."""

import json
import sys
import urllib.request
from pathlib import Path

import pytest

from distributed_training_sandbox_tpu.telemetry import (
    MetricsRegistry, TelemetryRun, read_clock_anchor, read_spans)
from distributed_training_sandbox_tpu.telemetry.spans import SpanStream

pytestmark = pytest.mark.obsplane

SCRIPTS = Path(__file__).resolve().parent.parent / "scripts"


def _fleet_timeline():
    sys.path.insert(0, str(SCRIPTS))
    import fleet_timeline
    return fleet_timeline


def _emit_at(stream: SpanStream, name: str, epoch_us: float,
             dur_s: float = 0.001, **attrs) -> None:
    """Record a span whose merged-timeline timestamp lands at the given
    absolute epoch microsecond — compensating for each stream's own
    anchor, so two streams created at different wall times still emit
    comparable arrivals."""
    start = stream._perf_anchor + (epoch_us - stream._epoch_us) / 1e6
    stream.record(name, cat="pump", start_perf=start,
                  end_perf=start + dur_s, **attrs)


# ---- satellite: bounded-error clock anchor, written lazily --------------

def test_clock_anchor_midpoint_sidecar_lazy(tmp_path):
    st = SpanStream(str(tmp_path))
    # lazy: no sidecar (and no spans.jsonl) until the first span
    assert not (tmp_path / "clock_anchor.json").exists()
    assert st.anchor_error_us >= 0.0
    st.record("pump/sync_every", cat="pump", step=0,
              start_perf=st._perf_anchor, end_perf=st._perf_anchor + 0.01)
    st.close()
    anchor = read_clock_anchor(str(tmp_path))
    assert anchor is not None and anchor["schema"] == 1
    # midpoint capture: the persisted pair reproduces the stream's
    # epoch<->perf mapping, with the half-window error bound alongside
    assert anchor["perf_anchor_s"] == st._perf_anchor
    assert anchor["epoch_us"] == st._epoch_us
    assert anchor["anchor_error_us"] == st.anchor_error_us
    assert anchor["rank"] == 0 and anchor["pid"] > 0
    # every span carries rank + pid so merged streams stay attributable
    (span,) = read_spans(str(tmp_path))
    assert span["rank"] == 0 and span["pid"] == anchor["pid"]


def test_rank_stamping_and_run_id_suffix(tmp_path, monkeypatch):
    """DTS_PROCESS_ID wins over jax.process_index() so launcher-spawned
    workers stamp their true rank: rank-N run ids get ``-rN``, the
    manifest carries rank + launch group, spans carry rank."""
    monkeypatch.setenv("DTS_PROCESS_ID", "3")
    monkeypatch.setenv("DTS_LAUNCH_GROUP", "grp-42")
    t = TelemetryRun("ddp", config={"num_steps": 1},
                     results_dir=str(tmp_path), run_name="stamp")
    t.start()
    with t.spans.span("pump/drain", cat="pump", step=0):
        pass
    t.step(loss=1.0)
    t.finalize()
    assert t.rank == 3 and t.run_id.endswith("-r3")
    man = json.loads((Path(t.run_dir) / "manifest.json").read_text())
    assert man["extra"]["rank"] == 3
    assert man["extra"]["launch_group"] == "grp-42"
    assert man["pid"] > 0
    (span,) = read_spans(t.run_dir)
    assert span["rank"] == 3
    assert read_clock_anchor(t.run_dir)["rank"] == 3
    steps = [json.loads(ln) for ln in
             (Path(t.run_dir) / "steps.jsonl").read_text().splitlines()]
    assert steps[0]["rank"] == 3


# ---- satellite: steps schema back-compat --------------------------------

def test_step_schema_tracing_fields_optional():
    """request_id / trace_id / rank are additive: version unchanged,
    absent on plain events, validated clean when present."""
    from distributed_training_sandbox_tpu.telemetry.schema import (
        STEP_SCHEMA_VERSION, step_event, validate_step)
    assert STEP_SCHEMA_VERSION == 1
    plain = step_event(0, loss=1.0)
    assert validate_step(plain) == []
    assert "trace_id" not in plain and "request_id" not in plain
    traced = step_event(1, loss=None, request_id=7, trace_id="tr-000007",
                        rank=1, phase="prefill")
    assert validate_step(traced) == []
    assert traced["schema"] == plain["schema"] == 1


# ---- live metrics registry + endpoint -----------------------------------

def test_metrics_registry_prometheus_render():
    m = MetricsRegistry()
    m.inc("steps_total")
    m.inc("steps_total", 2)
    m.inc("router_shed_total", reason="deadline")
    m.set("last_step_time_s", 0.25)
    m.observe("prefetch_wait_seconds", 0.004)
    assert m.counter_total("steps_total") == 3.0
    assert m.counter_total("router_shed_total") == 1.0
    text = m.render_prometheus()
    assert "# TYPE dts_steps_total counter" in text
    assert "dts_steps_total 3" in text
    assert 'dts_router_shed_total{reason="deadline"} 1' in text
    assert "# TYPE dts_last_step_time_s gauge" in text
    assert "# TYPE dts_prefetch_wait_seconds histogram" in text
    assert "dts_prefetch_wait_seconds_count 1" in text
    snap = m.snapshot()
    assert snap["counters"]["dts_steps_total"] == 3.0
    assert snap["gauges"]["dts_last_step_time_s"] == 0.25


def test_metrics_endpoint_scrape_matches_summary(tmp_path):
    """THE live-metrics acceptance: scraping ``/metrics`` mid-run
    returns valid Prometheus text, and the endpoint's final counters
    match the ``summary.json`` snapshot the run writes at exit."""
    t = TelemetryRun("ddp", config={"num_steps": 3},
                     results_dir=str(tmp_path), run_name="scrape",
                     metrics_port=0)
    t.start()
    assert t.metrics_server is not None and t.metrics_server.port > 0
    t.step(loss=1.0, tokens=128)
    mid = urllib.request.urlopen(t.metrics_server.url, timeout=5) \
        .read().decode()
    assert "# TYPE dts_steps_total counter" in mid
    assert "dts_steps_total 1" in mid
    t.step(loss=0.9, tokens=128)
    t.step(loss=0.8, tokens=128)
    final = t.metrics.snapshot()
    t.finalize()
    summary = json.loads((Path(t.run_dir) / "summary.json").read_text())
    assert summary["metrics"]["counters"] == final["counters"]
    assert summary["metrics"]["counters"]["dts_steps_total"] == 3.0
    assert summary["metrics"]["counters"]["dts_tokens_total"] == 384.0
    # the server is torn down and a last metrics.jsonl snapshot written
    assert t.metrics_server is None
    lines = (Path(t.run_dir) / "metrics.jsonl").read_text().splitlines()
    last = json.loads(lines[-1])
    assert last["counters"] == final["counters"] and "ts" in last


def test_metrics_off_keeps_exact_artifact_set(tmp_path):
    """No metrics_port -> no endpoint, no metrics.jsonl: the artifact
    set of a plain run is byte-for-byte the pre-obsplane one."""
    t = TelemetryRun("ddp", config={"num_steps": 1},
                     results_dir=str(tmp_path), run_name="plain")
    t.start()
    t.step(loss=1.0)
    t.finalize()
    assert t.metrics_server is None
    assert sorted(p.name for p in Path(t.run_dir).iterdir()) == \
        ["manifest.json", "steps.jsonl", "summary.json"]


# ---- HEADLINE: cross-rank merge + straggler attribution -----------------

def _two_rank_group(tmp_path, monkeypatch, lags_ms=(5.0, 12.0),
                    slow_rank=1):
    """Two TelemetryRuns standing in for the two workers of one launch
    group, with pump sync-site arrivals at controlled epoch offsets:
    ``slow_rank`` arrives ``lags_ms[step]`` late at step's site."""
    monkeypatch.setenv("DTS_LAUNCH_GROUP", "g-straggle")
    dirs = []
    t0_us = None
    for rank in (0, 1):
        monkeypatch.setenv("DTS_PROCESS_ID", str(rank))
        t = TelemetryRun("ddp", config={"num_steps": 2},
                         results_dir=str(tmp_path), run_name="merge")
        t.start()
        if t0_us is None:
            t0_us = t.spans._epoch_us + 2e6   # common grid, both anchors
        for step, lag in enumerate(lags_ms):
            off_us = lag * 1e3 if rank == slow_rank else 0.0
            _emit_at(t.spans, "pump/sync_every",
                     t0_us + step * 1e5 + off_us, step=step)
        t.step(loss=1.0)
        t.finalize()
        dirs.append(t.run_dir)
    return dirs


def test_fleet_timeline_merges_group_with_straggler_report(
        tmp_path, monkeypatch, capsys):
    FT = _fleet_timeline()
    dirs = _two_rank_group(tmp_path, monkeypatch)
    monkeypatch.delenv("DTS_PROCESS_ID")
    monkeypatch.delenv("DTS_LAUNCH_GROUP")

    groups = FT.discover_groups(str(tmp_path))
    assert list(groups) == ["g-straggle"] and len(groups["g-straggle"]) == 2

    assert FT.main(["--results-dir", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "straggler: rank 1" in out

    # ONE merged timeline document
    doc = json.loads((Path(dirs[0]) / "fleet_timeline.json").read_text())
    rep = doc["metadata"]["straggler_report"]
    # the report names the slowed rank, per site and overall
    assert rep["straggler"] == 1
    assert [s["last_rank"] for s in rep["sync_sites"]] == [1, 1]
    assert rep["sync_sites"][0]["lag_ms"] == pytest.approx(5.0, abs=0.5)
    assert rep["sync_sites"][1]["lag_ms"] == pytest.approx(12.0, abs=0.5)
    # the early rank eats the lag: blocked-on-peers sums both sites
    assert rep["per_rank"]["0"]["blocked_on_peers_ms"] == \
        pytest.approx(17.0, abs=1.0)
    assert rep["per_rank"]["1"]["blocked_on_peers_ms"] == \
        pytest.approx(0.0, abs=0.5)
    assert rep["per_rank"]["1"]["times_last"] == 2
    assert rep["max_anchor_error_us"] is not None
    # per-rank named process tracks
    names = {e["args"]["name"] for e in doc["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert any(n.startswith("rank 0") for n in names)
    assert any(n.startswith("rank 1") for n in names)
    # ordering contract: metadata first, then X events by ts
    evs = doc["traceEvents"]
    assert max(i for i, e in enumerate(evs) if e["ph"] == "M") < \
        min(i for i, e in enumerate(evs) if e["ph"] == "X")
    ts = [e["ts"] for e in evs if e["ph"] == "X"]
    assert ts == sorted(ts)


def test_discover_groups_ungrouped_runs_fall_back_to_run_id(tmp_path):
    """Pre-group run dirs (no launch_group stamped) still merge: the
    ``-rN`` suffix is stripped so N ranks of one launch share a key."""
    FT = _fleet_timeline()
    for rid in ("20260101-000000-ddp", "20260101-000000-ddp-r1"):
        d = tmp_path / rid
        d.mkdir()
        (d / "manifest.json").write_text(json.dumps(
            {"schema": 2, "run_id": rid, "strategy": "ddp", "extra": {}}))
    groups = FT.discover_groups(str(tmp_path))
    assert list(groups) == ["20260101-000000-ddp"]
    assert len(groups["20260101-000000-ddp"]) == 2


# ---- HEADLINE: failover trace join + TTFT decomposition -----------------

def test_failover_trace_join_and_ttft_decomposition(tmp_path):
    """kill_replica mid-trace: the replayed request's spans land on BOTH
    replicas under the ORIGINAL trace_id, its swimlane is one track, and
    the TTFT decomposition uses the last (surviving) attempt only —
    queue_wait + prefill sums to the engine-measured TTFT."""
    import jax
    import numpy as np
    from distributed_training_sandbox_tpu.models import transformer as T
    from distributed_training_sandbox_tpu.serving import Fleet

    FT = _fleet_timeline()
    cfg = T.TINY_LM
    params = jax.tree.map(lambda x: (x * 3.0).astype(x.dtype),
                          T.init_params(jax.random.PRNGKey(0), cfg))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg.vocab_size, size=5).astype(np.int32)
               for _ in range(10)]
    arrivals = np.sort(rng.uniform(0.0, 0.3, size=10))
    arrivals[0] = 0.0

    t = TelemetryRun("fleet", config={"num_steps": 0},
                     results_dir=str(tmp_path), run_name="joiner")
    with t as telem:
        fleet = Fleet(params, cfg, replicas=2, watchdog_timeout_s=0.0,
                      fault="kill_replica@1:1", max_queue=16,
                      telem=telem, max_batch=2, page_size=8,
                      max_seq_len=32, prefill_chunk=8, sync_every=2)
        reqs = [fleet.submit(p, max_new_tokens=5, arrival_s=a)
                for p, a in zip(prompts, arrivals)]
        done = fleet.run()
        assert len(done) == 10
        telem.finalize(fleet=fleet.slo_report())

    # every request carries a router-minted trace id of the pinned shape
    by_tid = {r.trace_id: r for r in reqs}
    assert len(by_tid) == 10
    assert all(tid == f"tr-{r.rid:06d}" for tid, r in by_tid.items())

    spans = read_spans(t.run_dir)
    # the span that ends a request's prefill stamps its first token
    prefills = [s for s in spans if s.get("t_first_s") is not None]
    assert prefills and all(s["name"] == "serve/bookkeep"
                            and "trace_id" in s and "replica" in s
                            for s in prefills)
    replicas_of = {}
    for s in prefills:
        replicas_of.setdefault(s["trace_id"], set()).add(s["replica"])
    replayed = {tid for tid, reps in replicas_of.items() if len(reps) > 1}
    # the killed replica had in-flight work: >= 1 trace spans replicas
    assert replayed, replicas_of
    assert all(replicas_of[tid] == {0, 1} for tid in replayed)

    report = {q["trace_id"]: q
              for q in FT.request_report([{"rank": 0, "spans": spans}])}
    assert set(report) == set(by_tid)
    for tid, q in report.items():
        req = by_tid[tid]
        # replay counted ONCE: decomposition from the last attempt sums
        # to the engine-measured TTFT of the request object
        measured_ms = (req.t_first - req.t_submit) * 1e3
        assert q["ttft_ms"] == pytest.approx(measured_ms, abs=0.01)
        assert q["queue_wait_ms"] + q["prefill_ms"] == \
            pytest.approx(q["ttft_ms"], abs=0.01)
        assert q["replayed"] == (tid in replayed)
        assert q["attempts"] == len(
            [s for s in prefills if s["trace_id"] == tid])

    # merged doc: a "requests" process whose swimlane threads are one
    # tid per trace — the replayed trace's events interleave replicas
    doc = FT.merge_timeline([t.run_dir])
    req_events = [e for e in doc["traceEvents"]
                  if e.get("pid") == FT.REQUEST_PID and e["ph"] == "X"]
    lanes = {}
    for e in req_events:
        lanes.setdefault(e["args"]["trace_id"], set()).add(e["tid"])
    assert all(len(tids) == 1 for tids in lanes.values())
    for tid in replayed:
        reps = {e["args"].get("replica") for e in req_events
                if e["args"]["trace_id"] == tid
                and e["name"] == "serve/prefill_sync"}
        assert reps == {0, 1}
    assert doc["metadata"]["requests"]
    # and the steps.jsonl serving rows carry the optional tracing fields
    rows = [json.loads(ln) for ln in
            (Path(t.run_dir) / "steps.jsonl").read_text().splitlines()]
    pf = [r for r in rows if r.get("phase") == "prefill"]
    assert pf and all(r.get("trace_id") for r in pf)
    completed = [r for row in rows
                 for r in (row.get("completed_requests") or [])]
    assert completed and all(c.get("trace_id") for c in completed)


# ---- run registry + cost model ------------------------------------------

def _runs_mod():
    sys.path.insert(0, str(SCRIPTS))
    import runs
    return runs


def _fake_indexed_run(root: Path, name: str, step_ms: float,
                      busbw: float, total_us: float) -> Path:
    from distributed_training_sandbox_tpu.telemetry.ledger import (
        payload_bucket)
    bucket = payload_bucket(2 * 1024 * 1024)   # "≤2MiB"
    d = root / name
    d.mkdir(parents=True)
    (d / "manifest.json").write_text(json.dumps(
        {"schema": 2, "run_id": name, "strategy": "ddp", "model": "TINY",
         "started_utc": f"2026-08-05T00:0{name[-1]}:00", "device_count": 8,
         "extra": {"rank": 0, "launch_group": "g1"}}))
    (d / "summary.json").write_text(json.dumps(
        {"run_id": name, "status": "completed", "steps_recorded": 10,
         "total_tokens": 1000, "step_time_ms": step_ms,
         "tokens_per_second": 1000.0 / step_ms, "final_loss": 2.0,
         "host_sync_count": 3}))
    bytes_moved = busbw * 1e3 * total_us       # GB/s = bytes/us / 1e3
    (d / "collectives.json").write_text(json.dumps(
        {"schema": 1, "aggregates": {
            f"all_reduce|{bucket}|data": {
                "kind": "all_reduce", "payload_bucket": bucket,
                "axis": "data", "sites": 2, "events": 20,
                "total_us": total_us, "bytes_moved": bytes_moved,
                "bus_bytes_moved": bytes_moved * 1.75,
                "algbw_gbps": busbw, "busbw_gbps": busbw * 1.75}}}))
    return d


def test_runs_registry_index_list_show_diff(tmp_path, capsys):
    R = _runs_mod()
    root = tmp_path / "runs"
    for name, ms, bw, us in (("run1", 100.0, 10.0, 5000.0),
                             ("run2", 90.0, 12.0, 4000.0),
                             ("run3", 110.0, 11.0, 6000.0)):
        _fake_indexed_run(root, name, ms, bw, us)
    db = str(tmp_path / "runs.sqlite")
    assert R.main(["--db", db, "index", "--results-dir", str(root)]) == 0
    assert R.main(["--db", db, "list", "--group", "g1"]) == 0
    out = capsys.readouterr().out
    assert "indexed 3 run(s)" in out and "run2" in out

    assert R.main(["--db", db, "show", "run2"]) == 0
    out = capsys.readouterr().out
    assert "busbw=21.0 GB/s" in out

    conn = R.connect(db)
    d = R.diff_runs(conn, "run1", "run2")
    assert d["metrics"]["step_time_ms"]["verdict"] == "improved"
    assert d["metrics"]["tokens_per_second"]["verdict"] == "improved"
    assert d["metrics"]["final_loss"]["verdict"] == "flat"
    (key,) = d["busbw"]
    assert d["busbw"][key]["delta_gbps"] == pytest.approx(3.5)
    conn.close()
    # regression direction flips the verdict — and gates the exit code
    assert R.main(["--db", db, "diff", "run2", "run3",
                   "--fail-on-regression"]) == 1
    capsys.readouterr()
    # unknown run fails loudly, not with an empty diff
    with pytest.raises(KeyError, match="not indexed"):
        R.diff_runs(R.connect(db), "run1", "nope")


def test_cost_model_export_roundtrip(tmp_path, capsys):
    """THE registry acceptance: fold >= 3 indexed runs' ledger
    aggregates into cost_model.json (time-weighted, not mean-of-means)
    and round-trip it through the loader."""
    R = _runs_mod()
    root = tmp_path / "runs"
    shapes = (("run1", 100.0, 10.0, 5000.0), ("run2", 90.0, 12.0, 4000.0),
              ("run3", 110.0, 11.0, 6000.0))
    for name, ms, bw, us in shapes:
        _fake_indexed_run(root, name, ms, bw, us)
    db = str(tmp_path / "runs.sqlite")
    R.main(["--db", db, "index", "--results-dir", str(root)])
    out_path = str(tmp_path / "cost_model.json")
    assert R.main(["--db", db, "export-cost-model",
                   "--out", out_path]) == 0

    cm = R.load_cost_model(out_path)
    assert sorted(cm.runs) == ["run1", "run2", "run3"]
    from distributed_training_sandbox_tpu.telemetry.ledger import (
        payload_bucket)
    bucket = payload_bucket(2 * 1024 * 1024)
    # time-weighted fold: total bus bytes over total time
    bus = sum(bw * 1e3 * us * 1.75 for _, _, bw, us in shapes)
    t = sum(us for _, _, _, us in shapes)
    assert cm.busbw_gbps("all_reduce", bucket, "data") == \
        pytest.approx(bus / t / 1e3, rel=1e-4)
    # the autotuner-facing query resolves the bucket from a byte count
    est = cm.estimate_us("all_reduce", 2 * 1024 * 1024, "data")
    assert est == pytest.approx(
        2 * 1024 * 1024 / (cm.busbw_gbps("all_reduce", bucket, "data")
                           * 1e3), rel=1e-6)
    assert cm.busbw_gbps("all_gather", bucket, "data") is None
    assert cm.estimate_us("all_gather", 64, "data") is None

    # < min_runs refuses: one noisy run must not become the cost model
    capsys.readouterr()
    assert R.main(["--db", db, "export-cost-model", "--out", out_path,
                   "run1", "run2"]) == 2
    assert "needs >= 3 runs" in capsys.readouterr().err


def test_cost_model_schema_version_drift_fails_loudly(tmp_path):
    """The export carries a pinned ``schema_version`` and every loader
    path — the registry's own CostModel AND the tuner's assembled cost
    model — refuses a drifted doc instead of silently mis-ranking."""
    R = _runs_mod()
    root = tmp_path / "runs"
    for name, ms, bw, us in (("run1", 100.0, 10.0, 5000.0),
                             ("run2", 90.0, 12.0, 4000.0),
                             ("run3", 110.0, 11.0, 6000.0)):
        _fake_indexed_run(root, name, ms, bw, us)
    db = str(tmp_path / "runs.sqlite")
    R.main(["--db", db, "index", "--results-dir", str(root)])
    out_path = str(tmp_path / "cost_model.json")
    assert R.main(["--db", db, "export-cost-model",
                   "--out", out_path]) == 0
    doc = json.loads(Path(out_path).read_text())
    assert doc["schema_version"] == R.COST_MODEL_SCHEMA

    # bumped version -> ValueError at construction, naming the re-export
    for bad in ({**doc, "schema_version": doc["schema_version"] + 1,
                 "schema": doc["schema_version"] + 1},
                {k: v for k, v in doc.items()
                 if k not in ("schema_version", "schema")}):
        with pytest.raises(ValueError, match="schema_version"):
            R.CostModel(bad)

    # the tuner's loader goes through the same gate: a drifted file on
    # disk raises out of from_artifacts rather than degrading silently
    from distributed_training_sandbox_tpu.tuner import TunerCostModel
    bad_path = tmp_path / "cost_model_drifted.json"
    bad_path.write_text(json.dumps(
        {**doc, "schema_version": 99, "schema": 99}))
    with pytest.raises(ValueError, match="schema_version"):
        TunerCostModel.from_artifacts(cost_model_path=str(bad_path),
                                      prior_paths=[])
    # the good file loads and prices through the tuner surface
    tcm = TunerCostModel.from_artifacts(cost_model_path=out_path,
                                        prior_paths=[])
    assert tcm.cost_model is not None
    assert tcm.cost_model.busbw_gbps(
        "all_reduce", "≤2MiB", "data") is not None


# ---- satellite: span-name cardinality lint ------------------------------

def test_span_name_not_static_lint_red_green():
    from distributed_training_sandbox_tpu.analysis.pitfalls import (
        lint_source)
    red = (
        "def f(spans, m, rid):\n"
        "    with maybe_span(spans, f'req/{rid}', cat='serve'):\n"
        "        pass\n"
        "    spans.record(f'serve/{rid}', start_perf=0, end_perf=1)\n"
        "    m.metrics.inc('done_' + str(rid))\n"
        "    m.metrics.observe(name * 2, 0.5)\n"
    )
    findings = lint_source(red, "red.py")
    hits = [f for f in findings if f.check == "span-name-not-static"]
    assert [f.line for f in hits] == [2, 4, 5, 6]
    assert all(f.severity == "error" for f in hits)
    green = (
        "def f(spans, m, reason):\n"
        "    with maybe_span(spans,  # span-ok\n"
        "                    f'pump/{reason}', cat='pump'):\n"
        "        pass\n"
        "    spans.record('serve/prefill_chunk', start_perf=0, end_perf=1)\n"
        "    m.metrics.inc('steps_total')\n"
        "    maybe_observe(m.metrics, 'prefetch_wait_seconds', 0.1)\n"
    )
    assert [f for f in lint_source(green, "green.py")
            if f.check == "span-name-not-static"] == []


def test_emitting_trees_sweep_clean():
    """Every tree that emits telemetry stays clean under the
    cardinality lint (pragmas only at the documented forwarders), and
    launch/ stays clean under the swallowed-error sweep — the pin
    behind lint_sharding.py's extended main()."""
    from distributed_training_sandbox_tpu.analysis.pitfalls import (
        lint_tree)
    pkg = Path(__file__).resolve().parent.parent \
        / "distributed_training_sandbox_tpu"
    for sub in ("telemetry", "runtime", "serving"):
        assert lint_tree(pkg / sub, recursive=True,
                         checks={"span-name-not-static"}) == [], sub
    assert [f for f in lint_tree(pkg / "launch", recursive=True,
                                 checks={"swallowed-distributed-error",
                                         "host-sync-in-loop"})
            if f.severity == "error"] == []


# ---- satellite: export_timeline ordering --------------------------------

def test_export_timeline_sorted_with_named_tracks(tmp_path):
    t = TelemetryRun("ddp", config={"num_steps": 1},
                     results_dir=str(tmp_path), run_name="order")
    t.start()
    # record out of order: the exporter must sort
    _emit_at(t.spans, "pump/drain", t.spans._epoch_us + 5e5, step=1)
    _emit_at(t.spans, "pump/sync_every", t.spans._epoch_us + 1e5, step=0)
    t.step(loss=1.0)
    t.finalize()
    sys.path.insert(0, str(SCRIPTS))
    import export_timeline as ET
    out = tmp_path / "timeline.json"
    assert ET.main([t.run_dir, "--out", str(out)]) == 0
    doc = json.loads(out.read_text())
    evs = doc["traceEvents"]
    m_idx = [i for i, e in enumerate(evs) if e.get("ph") == "M"]
    x_idx = [i for i, e in enumerate(evs) if e.get("ph") == "X"]
    assert m_idx and x_idx and max(m_idx) < min(x_idx)
    ts = [evs[i]["ts"] for i in x_idx]
    assert ts == sorted(ts)
    names = {e["args"]["name"] for e in evs
             if e.get("ph") == "M" and e["name"] == "process_name"}
    assert "host phases" in names


# ---- slow leg: REAL 2-process launch group ------------------------------

@pytest.mark.slow
def test_two_process_launch_merges_and_names_slowed_rank(tmp_path):
    """THE cross-rank acceptance, end-to-end: a real ``dts-launch run
    --nprocs 2`` group with ``--inject-fault slow@2:600`` restricted to
    rank 1 via DTS_FAULT_RANK merges into ONE fleet timeline with a
    named track per rank, and the straggler report names rank 1."""
    import os
    import subprocess

    results = tmp_path / "results"
    results.mkdir()
    env = dict(os.environ,
               RESULTS_DIR=str(results),
               DTS_FAULT_RANK="1")
    r = subprocess.run(
        [sys.executable, "-m",
         "distributed_training_sandbox_tpu.launch.cli", "run",
         "--script", "zero1", "--run-name", "straggle", "--num-steps", "4",
         "--devices", "cpu:2", "--nprocs", "2", "--trace-root",
         str(tmp_path / "traces"), "--",
         "--scale", "100", "--sync-every", "1",
         "--inject-fault", "slow@2:600"],
        capture_output=True, text=True, env=env, timeout=600,
        cwd=Path(__file__).parent.parent)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])

    FT = _fleet_timeline()
    # both ranks' run dirs landed under the shared results root and
    # carry the launcher-stamped group; merge ONE leg's pair explicitly
    # (the zero driver runs two telemetry legs per rank)
    groups = FT.discover_groups(str(results))
    assert groups, list(results.iterdir())
    leg_dirs = [d for d in sorted(results.iterdir())
                if (json.loads((d / "manifest.json").read_text())
                    ["strategy"]) == "zero1-baseline"]
    assert len(leg_dirs) == 2, list(results.iterdir())
    ranks = sorted(FT.load_rank_stream(str(d))["rank"] for d in leg_dirs)
    assert ranks == [0, 1]

    doc = FT.merge_timeline([str(d) for d in leg_dirs])
    rep = doc["metadata"]["straggler_report"]
    assert rep["ranks"] == [0, 1]
    assert rep["sync_sites"], "no shared pump sync sites recorded"
    # the injected 600 ms sleep on rank 1 dominates scheduler noise:
    # the report must name the slowed rank
    assert rep["straggler"] == 1, rep
    assert rep["per_rank"]["0"]["blocked_on_peers_ms"] > 200.0, rep
    names = {e["args"]["name"] for e in doc["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert any(n.startswith("rank 0") for n in names)
    assert any(n.startswith("rank 1") for n in names)
