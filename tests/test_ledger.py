"""Collective ledger: trace⋈HLO bandwidth attribution, the measured
contract join, trace-file ownership, host spans + the merged timeline
export, and the bandwidth regression gate.

The deterministic half runs against checked-in fixtures
(``tests/fixtures/ledger/``: hand-built traces in the JSON form of
``trace_analysis.load_trace`` + the matching compiled-HLO text, numbers
chosen so every bandwidth is exact in float: ``trace.json`` /
``step.hlo.txt`` as the CPU simulator names things, ``trace_v5e.json`` /
``step_v5e.hlo.txt`` as XLA:TPU compiles and traces the four-chip FSDP
step, one instance of every form the join has a rule for).
The live half lowers the real strategy fixtures on the 8-way CPU mesh,
profiles a few steps, and demands the ledger account for every
contract-expected collective site — zero unmatched, zero unmeasured.
"""

import gzip
import json
import math
import os
import sys
from pathlib import Path

import pytest

from distributed_training_sandbox_tpu.ops.busbench import bus_factor
from distributed_training_sandbox_tpu.ops.hlo import collective_instances
from distributed_training_sandbox_tpu.telemetry.ledger import (
    CollectiveLedger, LedgerEntry, build_ledger, check_bandwidth_regressions,
    collective_sites, join_contract, load_ledger_dict, payload_bucket)
from distributed_training_sandbox_tpu.telemetry.spans import (
    SpanStream, maybe_span, read_spans)
from distributed_training_sandbox_tpu.utils.trace_analysis import (
    collective_event_stats, latest_xplane_file, normalize_event_name,
    profile_session_dirs)

pytestmark = pytest.mark.ledger

FIX = Path(__file__).parent / "fixtures" / "ledger"
SCRIPTS = Path(__file__).resolve().parent.parent / "scripts"
HLO = (FIX / "step.hlo.txt").read_text()
TRACE = str(FIX / "trace.json")
HLO_V5E = (FIX / "step_v5e.hlo.txt").read_text()
TRACE_V5E = str(FIX / "trace_v5e.json")


def fixture_stats():
    return collective_event_stats(TRACE)


# ------------------------------------------------------------ unit pieces

def test_payload_bucket():
    assert payload_bucket(0) == "0B"
    assert payload_bucket(4) == "≤4B"
    assert payload_bucket(4096) == "≤4KiB"
    assert payload_bucket(4097) == "≤8KiB"         # rounds up to pow-2
    assert payload_bucket(1 << 20) == "≤1MiB"
    assert payload_bucket((1 << 30) + 1) == "≤2GiB"


def test_normalize_event_name():
    assert normalize_event_name("all-reduce.1") == "all-reduce.1"
    assert normalize_event_name("%all-reduce.1") == "all-reduce.1"
    assert normalize_event_name("while/body/all-reduce.1") == "all-reduce.1"


def test_bus_factor_nccl_accounting():
    assert bus_factor("all_reduce", 8) == pytest.approx(2 * 7 / 8)
    assert bus_factor("all_gather", 8) == pytest.approx(7 / 8)
    assert bus_factor("reduce_scatter", 8) == pytest.approx(7 / 8)
    assert bus_factor("ppermute", 8) == 1.0
    assert bus_factor("collective_permute", 8) == 1.0
    assert bus_factor("all_reduce", 1) == 1.0      # degenerate group


# ----------------------------------------------- fixture trace ⋈ fixture HLO

def test_fixture_event_stats_pool_an_instructions_events():
    """Every plane's events of one instruction pool into one record (the
    name forms an event may carry are ``test_normalize_event_name``'s)."""
    stats = fixture_stats()
    # 8 planes once + 4 of them twice more = 16 events of all-reduce.1
    assert stats["all-reduce.1"] == {"count": 16, "total_us": 160.0}
    assert stats["all-gather.2"]["count"] == 8
    # a wait half whose start the window missed is its own record...
    assert stats["all-reduce-done.9"]["count"] == 2
    # ...and non-collective events (fusion/copy) never appear
    assert not any(n.startswith(("fusion", "copy")) for n in stats)


def test_fixture_hlo_instances():
    inst = {i.name: i for i in collective_instances(HLO)}
    assert set(inst) == {"all-reduce.1", "all-gather.2",
                         "reduce-scatter.3", "collective-permute.4"}
    assert inst["all-reduce.1"].bytes == 4096            # f32[1024]
    assert inst["all-gather.2"].bytes == 8192            # f32[8,256]
    # iota form [1,8]<=[8] expands to one group of 8
    assert inst["all-gather.2"].replica_groups == (tuple(range(8)),)
    assert inst["reduce-scatter.3"].bytes == 512         # output shard


def test_build_ledger_bandwidth_math():
    led = build_ledger(fixture_stats(), HLO, {"dp": 8})
    assert not led.unmatched_events and not led.unmeasured_instances
    assert led.async_done_us == 6.0
    by = {e.name: e for e in led.entries}

    ar = by["all-reduce.1"]
    assert (ar.kind, ar.occurrences, ar.mean_us) == ("all_reduce", 16, 10.0)
    assert ar.payload_bytes == 4096 and ar.axis == "dp"
    assert ar.algbw_gbps == pytest.approx(4096 / 10.0 / 1e3)
    assert ar.busbw_gbps == pytest.approx(ar.algbw_gbps * 2 * 7 / 8)

    # reduce_scatter messages are sized output × group (nccl-tests terms)
    rs = by["reduce-scatter.3"]
    assert rs.payload_bytes == 512 * 8
    assert rs.algbw_gbps == pytest.approx(4096 / 5.0 / 1e3)
    assert rs.busbw_gbps == pytest.approx(rs.algbw_gbps * 7 / 8)

    cp = by["collective-permute.4"]
    assert cp.busbw_gbps == cp.algbw_gbps == pytest.approx(0.256)


def test_aggregates_are_time_weighted():
    led = build_ledger(fixture_stats(), HLO, {"dp": 8})
    aggs = led.aggregates()
    key = "all_reduce|≤4KiB|dp"
    assert key in aggs
    a = aggs[key]
    assert a["sites"] == 1 and a["events"] == 16
    # total bytes over total time, not mean of per-site means
    assert a["algbw_gbps"] == pytest.approx(4096 * 16 / 160.0 / 1e3)
    tot = led.totals()
    assert tot["measured_sites"] == 4
    assert tot["unmatched_events"] == 0 and tot["unmeasured_sites"] == 0
    assert tot["async_done_us"] == 6.0


# ------------------------------------------------------- contract join

EXPECTED = {"all_reduce": 1, "all_gather": 1, "reduce_scatter": 1,
            "collective_permute": 1}


def test_join_contract_matched():
    led = build_ledger(fixture_stats(), HLO, {"dp": 8})
    v = join_contract(led, EXPECTED, "fixture")
    assert v["ok"] and not v["violations"]
    assert v["compiled_sites"] == v["measured_sites"]
    assert led.contract_join is v


def test_join_contract_unmatched_measured():
    """A collective-named trace event with no instruction in the program
    (another run's trace) must fail the join."""
    stats = fixture_stats()
    stats["all-reduce.99"] = {"count": 8, "total_us": 80.0}
    led = build_ledger(stats, HLO, {"dp": 8})
    assert "all-reduce.99" in led.unmatched_events
    v = join_contract(led, EXPECTED, "fixture")
    assert not v["ok"]
    assert v["unmatched_measured"] == ["all-reduce.99"]
    assert any("outside the program" in s for s in v["violations"])


def test_join_contract_missing_expected():
    """A program collective the trace never saw (profiler window missed
    it) must fail the join and be named."""
    stats = fixture_stats()
    del stats["all-gather.2"]
    led = build_ledger(stats, HLO, {"dp": 8})
    assert [r["name"] for r in led.unmeasured_instances] == ["all-gather.2"]
    v = join_contract(led, EXPECTED, "fixture")
    assert not v["ok"]
    assert v["missing_from_trace"] == ["all-gather.2"]
    # compiled sites still count the unmeasured instruction
    assert v["compiled_sites"]["all_gather"] == 1
    assert v["measured_sites"].get("all_gather", 0) == 0


def test_join_contract_range_violation():
    led = build_ledger(fixture_stats(), HLO, {"dp": 8})
    v = join_contract(led, dict(EXPECTED, all_reduce=0), "fixture")
    assert not v["ok"]
    assert any("1 compiled sites, contract allows 0..0" in s
               for s in v["violations"])
    # XLA's combiners merge lowered sites: one compiled all-reduce
    # satisfies a contract that lowered two to four
    assert join_contract(led, dict(EXPECTED, all_reduce="2..4"),
                         "fixture")["ok"]
    # "any" never constrains
    assert join_contract(led, dict(EXPECTED, all_reduce="any"),
                         "fixture")["ok"]


# ---------------------------- the four-chip FSDP step as XLA:TPU runs it

EXPECTED_V5E = {"all_reduce": 1, "all_gather": 2, "reduce_scatter": 2,
                "collective_permute": 0}


@pytest.mark.parametrize("name, kind, payload, compiler_made", [
    # an async collective fusion: start, continuation and done hold a copy
    # of ONE all-gather; the site is the start's
    ("async-collective-start", "all_gather", 2048 * 512 * 2, False),
    # synchronous instructions under their own names; a reduce-scatter's
    # message is its output times the group
    ("all-gather.247", "all_gather", 2048 * 2, False),
    ("reduce_scatter.196", "reduce_scatter", 2048 * 11008 * 2, False),
    # an all-reduce-scatter fusion: the fusion's name, the all-reduce's
    # (padded) tensor, a reduce_scatter
    ("fusion.371", "reduce_scatter", 2112 * 2048 * 2, True),
    # tuple shapes whose layouts hold parentheses; a permute-start's
    # message is its result alone, a combined all-reduce's every array
    ("collective-permute-start.1", "collective_permute", 48 * 2048 * 2,
     True),
    ("all-reduce.19", "all_reduce", 2 * 2048 * 2, True),
    ("psum.7", "all_reduce", 4, False),
])
def test_collective_sites_name_what_executes(name, kind, payload,
                                             compiler_made):
    sites = {s.name: s for s in collective_sites(HLO_V5E)}
    assert len(sites) == 7          # of 11 collective instructions
    s = sites[name]
    assert (s.kind, s.payload_bytes, s.compiler_made) \
        == (kind, payload, compiler_made)
    assert bool(s.path) != compiler_made


def test_the_join_holds_on_the_tpu_forms():
    """Every site measured, nothing measured outside the program, and the
    compiler's own collectives (a fused reduce-scatter with no op_name,
    the halo permute, the combined all-reduce) listed and measured but
    not held against the contract's count."""
    led = build_ledger(collective_event_stats(TRACE_V5E), HLO_V5E, {"dp": 4})
    v = join_contract(led, EXPECTED_V5E, "fsdp")
    assert v["ok"], v["violations"]
    assert v["missing_from_trace"] == [] and v["unmatched_measured"] == []
    assert v["compiled_sites"] == {"all_gather": 2, "reduce_scatter": 1,
                                   "all_reduce": 1}
    assert v["compiler_made_sites"] == 3
    by = {e.name: e for e in led.entries}
    # in flight from the start's beginning to the done's end: 1.06 ms
    ag = by["async-collective-start"]
    assert (ag.occurrences, ag.mean_us, ag.group_size) == (4, 1060.0, 4)
    assert ag.busbw_gbps == pytest.approx(2097152 / 1060.0 / 1e3 * 3 / 4,
                                          rel=1e-4)
    rs = by["fusion.371"]
    assert rs.kind == "reduce_scatter" and rs.compiler_made
    assert rs.busbw_gbps == pytest.approx(8650752 / 100.0 / 1e3 * 3 / 4,
                                          rel=1e-4)
    # a while body's site executes once a trip: twice a chip here
    assert by["reduce_scatter.196"].occurrences == 4
    assert by["psum.7"].occurrences == 2


def test_a_contract_that_forbids_a_written_collective_still_fails():
    led = build_ledger(collective_event_stats(TRACE_V5E), HLO_V5E, {"dp": 4})
    v = join_contract(led, dict(EXPECTED_V5E, all_gather=1), "fsdp")
    assert not v["ok"]
    assert any("all_gather: 2 compiled sites, contract allows 1..1" in s
               for s in v["violations"])
    # the compiler's permute is no violation of "collective_permute: 0"
    assert not any("collective_permute" in s for s in v["violations"])


def test_a_chain_the_trace_missed_is_named_after_its_start():
    stats = collective_event_stats(TRACE_V5E)
    del stats["async-collective-start"]
    stats["fusion.999"] = {"count": 2, "total_us": 8.0}
    led = build_ledger(stats, HLO_V5E, {"dp": 4})
    v = join_contract(led, EXPECTED_V5E, "fsdp")
    assert v["missing_from_trace"] == ["async-collective-start"]
    assert v["unmatched_measured"] == ["fusion.999"]
    assert v["compiled_sites"]["all_gather"] == 2
    assert v["measured_sites"]["all_gather"] == 1


# ------------------------------------------------------ regression gate

def _aggs(busbw):
    return {"all_reduce|≤4KiB|dp": {
        "kind": "all_reduce", "payload_bucket": "≤4KiB", "axis": "dp",
        "sites": 1, "events": 16, "total_us": 160.0,
        "algbw_gbps": busbw / 1.75, "busbw_gbps": busbw}}


def test_check_bandwidth_regressions():
    res = check_bandwidth_regressions(_aggs(0.4), _aggs(1.0),
                                      max_drop_pct=20.0)
    assert len(res) == 1 and res[0]["regressed"]
    assert res[0]["delta_pct"] == pytest.approx(-60.0)
    # within tolerance / improvement -> not regressed
    assert not check_bandwidth_regressions(_aggs(0.9), _aggs(1.0))[0][
        "regressed"]
    assert not check_bandwidth_regressions(_aggs(1.4), _aggs(1.0))[0][
        "regressed"]
    # keys only on one side are skipped, not errors
    assert check_bandwidth_regressions(_aggs(1.0), {}) == []


def _write_run(root, run_id, busbw, join_ok=True):
    d = root / run_id
    d.mkdir(parents=True)
    man = {"schema": 1, "run_id": run_id, "strategy": "ddp",
           "model": "mlp", "device_count": 8, "platform": "cpu",
           "config": {"num_steps": 4, "batch_size": 8,
                      "sequence_length": 32},
           "contract": {"strategy": "ddp", "ok": True, "violations": []},
           "ledger": {"measured_sites": 1, "unmeasured_sites": 0,
                      "unmatched_events": 0, "busbw_gbps": busbw,
                      "ok": join_ok, "violations": []}}
    summ = {"schema": 1, "run_id": run_id, "strategy": "ddp",
            "model": "mlp", "status": "completed", "num_steps": 4,
            "batch_size": 8, "sequence_length": 32,
            "step_time_ms": 10.0, "tokens_per_second": 100.0}
    (d / "manifest.json").write_text(json.dumps(man))
    (d / "summary.json").write_text(json.dumps(summ))
    led = {"schema": 1, "axis_sizes": {"dp": 8},
           "totals": {"measured_sites": 1, "unmeasured_sites": 0,
                      "unmatched_events": 0, "events": 16,
                      "total_us": 160.0, "async_done_us": 0.0,
                      "busbw_gbps": busbw},
           "entries": [], "aggregates": _aggs(busbw),
           "unmatched_events": {}, "unmeasured_instances": [],
           "contract_join": {"strategy": "ddp", "ok": join_ok,
                             "violations": []}}
    (d / "collectives.json").write_text(json.dumps(led))
    return d


def _report_main():
    sys.path.insert(0, str(SCRIPTS))
    from report import main
    return main


def test_report_gate_fails_on_degraded_pair(tmp_path, capsys):
    """THE acceptance gate: --fail-on-bandwidth-regression exits nonzero
    for a synthetically degraded run pair, and passes a healthy one."""
    base = tmp_path / "base"
    cur = tmp_path / "cur"
    _write_run(base, "r0-ddp", busbw=1.0)
    _write_run(cur, "r1-ddp", busbw=0.4)           # -60 % busbw
    main = _report_main()
    rc = main([str(cur), "--baseline", str(base),
               "--fail-on-bandwidth-regression", "20"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "Collective busbw deltas" in out
    # same pair without the flag: the table renders, exit stays 0
    assert main([str(cur), "--baseline", str(base)]) == 0
    # healthy pair with the flag: 0
    cur2 = tmp_path / "cur2"
    _write_run(cur2, "r2-ddp", busbw=0.95)
    assert main([str(cur2), "--baseline", str(base),
                 "--fail-on-bandwidth-regression", "20"]) == 0


def test_report_renders_bandwidth_table(tmp_path, capsys):
    _write_run(tmp_path / "runs", "r0-ddp", busbw=1.0)
    main = _report_main()
    assert main([str(tmp_path / "runs")]) == 0
    out = capsys.readouterr().out
    assert "Collective bus bandwidth (ledger vs roofline vs NCCL" in out
    assert "⋈✓" in out                    # joined verdict beside static
    assert "v5e-8 ICI 50" in out          # checked-in NCCL reference row


def test_load_roofline_and_nccl_reference():
    from distributed_training_sandbox_tpu.telemetry.report import (
        _best_busbw, load_nccl_reference, load_roofline)
    root = Path(__file__).resolve().parent.parent / "baselines"
    nccl = load_nccl_reference(str(root / "nccl_reference.json"))
    assert any(r["hardware"].startswith("v5e-8") for r in nccl)
    roof = load_roofline(
        str(root / "busbench_cpu_8dev_harness_validation.json"))
    assert roof and all("busbw_gbps" in r for r in roof)
    # ledger kind names resolve against busbench's "ppermute" rows
    rows = [{"collective": "ppermute", "busbw_gbps": 2.5}]
    assert _best_busbw(rows, "collective_permute") == 2.5


def test_checked_in_busbench_baseline_is_dict_form():
    root = Path(__file__).resolve().parent.parent / "baselines"
    doc = json.loads(
        (root / "busbench_cpu_8dev_harness_validation.json").read_text())
    assert doc["schema"] == 1 and doc["harness_validation"] is True
    assert doc["devices"] == 8 and isinstance(doc["rows"], list)
    kinds = {r["collective"] for r in doc["rows"]}
    assert {"all_reduce", "all_gather", "reduce_scatter",
            "ppermute"} <= kinds


# ------------------------------------------------- trace-file ownership

def _fake_session(trace_dir, stamp, mtime):
    sd = trace_dir / "plugins" / "profile" / stamp
    sd.mkdir(parents=True)
    tf = sd / f"host.{stamp}.xplane.pb"
    tf.write_bytes(b"")
    os.utime(tf, (mtime, mtime))
    return str(sd), str(tf)


def test_owned_session_beats_newer_trace(tmp_path):
    """The misattribution hazard: a concurrent run's NEWER trace must
    lose to the session this run actually owns."""
    mine_sd, mine_tf = _fake_session(tmp_path, "2026_01_01_00_00_01",
                                     mtime=1000.0)
    _, other_tf = _fake_session(tmp_path, "2026_01_01_00_00_02",
                                mtime=2000.0)
    assert latest_xplane_file(str(tmp_path)) == other_tf     # bare mtime
    assert latest_xplane_file(str(tmp_path), session=mine_sd) == mine_tf
    # relative session names resolve against trace_dir too
    assert latest_xplane_file(
        str(tmp_path),
        session=os.path.join("plugins", "profile",
                             "2026_01_01_00_00_01")) == mine_tf
    assert profile_session_dirs(str(tmp_path)) == sorted(
        [mine_sd, os.path.dirname(other_tf)])


# ------------------------------------------- spans + timeline export

def test_span_stream_roundtrip(tmp_path):
    s = SpanStream(str(tmp_path), flush_every=1)
    with s.span("pump/sync_every", cat="pump", step=7):
        pass
    with maybe_span(s, "prefetch/wait", cat="prefetch"):
        pass
    with maybe_span(None, "never/written"):         # no-op guard
        pass
    s.close()
    spans = read_spans(str(tmp_path))
    assert [e["name"] for e in spans] == ["pump/sync_every",
                                          "prefetch/wait"]
    assert spans[0]["step"] == 7 and spans[0]["cat"] == "pump"
    assert all(e["dur_us"] >= 0 and e["ts_us"] > 0 for e in spans)
    # records after close are dropped, not errors
    s.record("late", start_perf=0.0, end_perf=1.0)
    assert len(read_spans(str(tmp_path))) == 2


def test_export_timeline_merges_host_and_device(tmp_path):
    sys.path.insert(0, str(SCRIPTS))
    import export_timeline as ET

    run = tmp_path / "run"
    sd = run / "trace" / "plugins" / "profile" / "2026_01_01_00_00_01"
    sd.mkdir(parents=True)
    dev_tf = sd / "host.trace.json.gz"
    with gzip.open(dev_tf, "wt") as f:
        json.dump({"traceEvents": [
            {"ph": "X", "name": "all-reduce.1", "pid": 0, "tid": 0,
             "ts": 5_000_000.0, "dur": 10.0}]}, f)
    (run / "manifest.json").write_text(json.dumps(
        {"run_id": "r", "profile_sessions": [str(sd)]}))
    s = SpanStream(str(run), flush_every=1)
    with s.span("pump/sync_every", cat="pump"):
        pass
    s.close()

    doc = ET.build_timeline(str(run))
    host = [e for e in doc["traceEvents"]
            if e.get("pid") == ET.HOST_PID and e.get("ph") == "X"]
    dev = [e for e in doc["traceEvents"]
           if e.get("pid") != ET.HOST_PID and e.get("ph") == "X"]
    assert [e["name"] for e in host] == ["pump/sync_every"]
    assert [e["name"] for e in dev] == ["all-reduce.1"]
    # each clock is independently rebased: both sides start near 0
    assert min(e["ts"] for e in host) == 0.0
    assert min(e["ts"] for e in dev) == 0.0

    out = run / "timeline.json.gz"
    assert ET.main([str(run), "--out", str(out)]) == 0
    merged = json.load(gzip.open(out, "rt"))
    assert merged["metadata"]["host_spans"] == 1
    # empty run dir: nothing to export -> exit 1; not a dir -> 2
    empty = tmp_path / "empty"
    empty.mkdir()
    assert ET.main([str(empty)]) == 1
    assert ET.main([str(tmp_path / "missing")]) == 2


# ------------------------------------------------------- lint --ledger

def test_lint_ledger_mode(tmp_path):
    sys.path.insert(0, str(SCRIPTS))
    from lint_sharding import check_ledger_run

    agree = _write_run(tmp_path, "agree-ddp", busbw=1.0, join_ok=True)
    assert check_ledger_run(str(agree)) == 0
    disagree = _write_run(tmp_path, "disagree-ddp", busbw=1.0,
                          join_ok=False)
    assert check_ledger_run(str(disagree)) == 1
    # missing ledger / missing manifest -> exit 2 (inputs absent)
    bare = tmp_path / "bare"
    bare.mkdir()
    (bare / "manifest.json").write_text(json.dumps(
        {"contract": {"ok": True}}))
    assert check_ledger_run(str(bare)) == 2
    assert check_ledger_run(str(tmp_path / "nope")) == 2


# ------------------------------------- live: the 5-strategy acceptance

LIVE_STRATEGIES = ("ddp", "zero3", "fsdp", "tp", "serve_decode")


@pytest.mark.parametrize("strategy", LIVE_STRATEGIES)
def test_live_ledger_accounts_for_every_contract_site(strategy, tmp_path):
    """Profile 2 real steps of the strategy fixture on the CPU mesh and
    demand the ledger account for every contract-expected collective
    site: zero unmatched events, zero unmeasured instructions, measured
    verdict ok."""
    import jax

    from distributed_training_sandbox_tpu.analysis import check_counts
    from distributed_training_sandbox_tpu.analysis.fixtures import (
        build_strategy)
    from distributed_training_sandbox_tpu.ops.hlo import count_collectives

    b = build_strategy(strategy)
    lowered = b.step.lower(*b.args)
    verdict = check_counts(b.contract,
                           count_collectives(lowered.as_text()), b.ctx)
    assert verdict.ok, verdict.summary()
    hlo = lowered.compile().as_text()

    args = b.args
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(2):
            out = b.step(*args)
            args = b.advance(args, out)
        jax.block_until_ready(out)

    tf = latest_xplane_file(str(tmp_path))
    assert tf is not None, "profiler wrote no trace"
    led = build_ledger(collective_event_stats(tf), hlo,
                       dict(b.mesh.shape))
    join = join_contract(led, verdict.expected, strategy)
    assert join["ok"], join["violations"]
    assert led.unmatched_events == {}
    assert led.unmeasured_instances == []
    assert led.entries, "no collective was measured"
    # tiny scalar collectives can round to 0.0000 GB/s; the payload-
    # carrying sites must not
    assert max(e.busbw_gbps for e in led.entries) > 0
    assert all(e.busbw_gbps >= 0 and e.mean_us > 0 for e in led.entries)
    # the artifact round-trips through collectives.json
    led.write(str(tmp_path))
    doc = load_ledger_dict(str(tmp_path))
    assert doc["contract_join"]["ok"]
    assert doc["totals"]["measured_sites"] == len(led.entries)
