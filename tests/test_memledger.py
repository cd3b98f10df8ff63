"""Memory ledger suite (the ``memledger`` marker, tier-1): measured HBM
attribution joined to planner waterlines.

The deterministic half runs against a checked-in compiled-HLO fixture
(``tests/fixtures/memledger/step.hlo.txt`` — collective sites plus
``checkpoint_name`` metadata lines, byte counts chosen so every category
split is exact), synthetic ``memory_analysis()`` dicts, and synthetic
run dirs for the CI gates.  The live half compiles the real strategy
fixtures on the 8-way CPU mesh and demands the measured ledger peak land
inside the pinned band of both the compiled waterline and the analytic
predictor across remat policies — the substrate-honest acceptance: on
the stat-less CPU allocator the measured peak degrades to the accounted
waterline (``measured_source="accounted"``, compiled ratio exactly 1).
"""

import json
import os
import sys
import types
from pathlib import Path

import pytest

from distributed_training_sandbox_tpu.telemetry import memledger as ML
from distributed_training_sandbox_tpu.telemetry.memledger import (
    DEFAULT_BAND, MEMORY_FILENAME, PREDICTION_BANDS, MemoryLedger,
    MemorySampler, attribute_categories, build_memory_ledger,
    check_memory_regressions, get_sampler, join_prediction,
    load_memory_dict, memory_aggregates, param_path_bytes, phase_for_span,
    reset_sampler, saved_activation_bytes)
from distributed_training_sandbox_tpu.utils.memory import GB

pytestmark = pytest.mark.memledger

FIX = Path(__file__).parent / "fixtures" / "memledger"
SCRIPTS = Path(__file__).resolve().parent.parent / "scripts"
HLO = (FIX / "step.hlo.txt").read_text()

# the fixture's exact byte inventory (see step.hlo.txt):
#   collectives: all-reduce f32[1024]=4096 + all-gather f32[8,256]=8192
#                + collective-permute f32[256]=1024
#                + reduce-scatter shard f32[128]=512         = 13824
#   saved:       q_proj f32[8,32,64]=65536 + f32[16]=64
#                + attn_out bf16[4,128]=1024                 = 66624
FIX_SCRATCH = 13824
FIX_SAVED = 66624


# --------------------------------------------------------- unit pieces

def test_phase_for_span_vocabulary():
    assert phase_for_span("prefetch/wait", "prefetch") == "prefetch"
    assert phase_for_span("prefetch/next", None) == "prefetch"
    assert phase_for_span("checkpoint/save", "checkpoint") == "checkpoint"
    assert phase_for_span("prefill/chunk", None) == "prefill"
    assert phase_for_span("decode_burst", None) == "decode"
    # the engine's round samples once per device sync, not per span
    assert phase_for_span("serve/prefill_sync", None) == "prefill"
    assert phase_for_span("serve/burst_sync", None) == "decode"
    assert phase_for_span("serve/prefill_stage", None) is None
    assert phase_for_span("serve/bookkeep", None) is None
    assert phase_for_span("pump/sync_every", "pump") == "sync"
    assert phase_for_span("pump/drain", "pump") == "sync"
    assert phase_for_span("pump/dispatch", "pump") == "dispatch"
    # spans outside the memory timeline map to no phase
    assert phase_for_span("writer/flush", None) is None
    assert phase_for_span("", None) is None
    for ph in ("prefetch", "dispatch", "sync", "checkpoint",
               "prefill", "decode"):
        assert ph in ML.PHASES


def test_normalize_name_matches_ledger_convention():
    """Same normalization the collective ledger applies to trace events:
    leading % and scope prefixes stripped."""
    assert ML._normalize_name("%layers.w_up") == "layers.w_up"
    assert ML._normalize_name("while/body/layers.w_up") == "layers.w_up"
    assert ML._normalize_name("plain") == "plain"


def test_param_path_bytes_ranks_and_caps():
    import numpy as np
    tree = {"layers": {"w_up": np.zeros((64, 128), np.float32),
                       "w_down": np.zeros((128, 64), np.float32)},
            "emb": np.zeros((8,), np.float32)}
    got = param_path_bytes(tree)
    assert got["layers.w_up"] == 64 * 128 * 4
    assert got["layers.w_down"] == 128 * 64 * 4
    assert got["emb"] == 32
    # largest-first, then name; `top` caps the table
    assert list(got)[:2] == ["layers.w_down", "layers.w_up"]
    assert list(param_path_bytes(tree, top=1)) == ["layers.w_down"]


# ------------------------------------------------- fixture attribution

def test_saved_activation_bytes_fixture():
    """checkpoint_name metadata → result-shape bytes; duplicate save
    names pool their bytes but appear once; plain op_name lines and
    layout suffixes don't confuse the parse."""
    total, names = saved_activation_bytes(HLO)
    assert total == FIX_SAVED
    assert names == ["q_proj", "attn_out"]
    # compiles that drop the metadata degrade to (0, []) — the
    # "where available" half of the contract
    assert saved_activation_bytes("") == (0, [])
    assert saved_activation_bytes(
        '%x = f32[8]{0} copy(%y), metadata={op_name="jit(f)/mul"}'
    ) == (0, [])


def test_attribute_categories_fixture_split_is_exact():
    mem = {"argument_bytes": 50_000, "output_bytes": 2_000,
           "temp_bytes": 100_000, "alias_bytes": 0}
    cats, names = attribute_categories(
        mem, {"params": 30_000, "opt_state": 15_000}, HLO)
    assert cats == {
        "params": 30_000, "opt_state": 15_000,
        "unattributed_args": 5_000,                  # args − Σtrees
        "out": 2_000,
        "collective_scratch": FIX_SCRATCH,
        "saved_activations": FIX_SAVED,
        "activations_workspace": 100_000 - FIX_SCRATCH - FIX_SAVED,
    }
    assert names == ["q_proj", "attn_out"]
    # categories partition args and temps exactly
    assert (cats["params"] + cats["opt_state"]
            + cats["unattributed_args"]) == mem["argument_bytes"]
    assert (cats["collective_scratch"] + cats["saved_activations"]
            + cats["activations_workspace"]) == mem["temp_bytes"]


def test_attribute_categories_clamps_never_negative():
    """Donated/aliased compiles can report temps smaller than the HLO's
    nominal scratch; global tree bytes can exceed the per-device
    argument slice on a sharded mesh.  Both clamp, neither goes
    negative."""
    cats, _ = attribute_categories(
        {"argument_bytes": 1_000, "output_bytes": 0,
         "temp_bytes": 5_000, "alias_bytes": 0},
        {"params": 4_000}, HLO)
    assert cats["unattributed_args"] == 0            # trees > args
    assert cats["collective_scratch"] == 5_000       # min(scratch, temp)
    assert cats["saved_activations"] == 0            # temp exhausted
    assert cats["activations_workspace"] == 0
    assert all(v >= 0 for v in cats.values())


# ------------------------------------------------------------- sampler

def test_get_sampler_is_process_wide_and_shared(monkeypatch):
    """THE satellite pin: one shared poll site.  ``utils.tracker`` and
    ``utils.memory.all_devices_memory_gb`` must route through the same
    object ``get_sampler()`` returns."""
    from distributed_training_sandbox_tpu.utils import memory as UM
    from distributed_training_sandbox_tpu.utils.tracker import (
        PerformanceTracker)

    s = get_sampler()
    assert get_sampler() is s
    before = s.snapshot()["samples"]
    tr = PerformanceTracker()
    tr._sample_memory()
    snap = s.snapshot()
    assert snap["samples"] == before + 1
    # tracker samples land in the dispatch phase of the timeline
    assert "dispatch" in snap["phase_peaks_gb"]

    seen = {}
    monkeypatch.setattr(ML.MemorySampler, "all_devices_gb",
                        lambda self: seen.setdefault("self", self) or
                        {"0": {"current_gb": 0.0, "peak_gb": 0.0}})
    UM.all_devices_memory_gb()
    assert seen["self"] is s


def test_sampler_folds_global_and_phase_peaks(monkeypatch):
    feed = iter([
        {"bytes_in_use": 1 * GB, "peak_bytes_in_use": 2 * GB},
        {"bytes_in_use": 5 * GB, "peak_bytes_in_use": 3 * GB},
        {"bytes_in_use": 1 * GB, "peak_bytes_in_use": 4 * GB},
    ])
    monkeypatch.setattr(ML, "device_memory_stats", lambda *a: next(feed))
    s = MemorySampler()
    s.sample(phase="dispatch")
    s.sample(phase="dispatch")              # max(in_use, peak) = 5
    s.sample(phase="checkpoint")
    snap = s.snapshot()
    assert snap["samples"] == 3
    assert snap["peak_gb"] == pytest.approx(5.0)
    assert snap["phase_peaks_gb"]["dispatch"] == pytest.approx(5.0)
    assert snap["phase_peaks_gb"]["checkpoint"] == pytest.approx(4.0)
    s.reset()
    assert s.snapshot() == {"samples": 0, "peak_gb": 0.0,
                            "phase_peaks_gb": {}}


def test_span_stream_feeds_sampler_per_phase(tmp_path):
    from distributed_training_sandbox_tpu.telemetry.spans import SpanStream
    s = MemorySampler()
    st = SpanStream(str(tmp_path), flush_every=1)
    st.sampler = s
    with st.span("pump/sync_every", cat="pump"):
        pass
    with st.span("prefetch/wait", cat="prefetch"):
        pass
    with st.span("writer/flush"):           # no phase → not sampled
        pass
    st.close()
    snap = s.snapshot()
    assert snap["samples"] == 2
    assert set(snap["phase_peaks_gb"]) == {"sync", "prefetch"}


# ------------------------------------------------ ledger + the verdict

def _mem(args=50_000, out=2_000, temp=100_000, alias=0):
    return {"argument_bytes": args, "output_bytes": out,
            "temp_bytes": temp, "alias_bytes": alias}


def test_build_memory_ledger_accounted_fallback_and_roundtrip(tmp_path):
    """Stat-less backend: measured peak degrades to the accounted
    waterline; memory.json round-trips through load + the gate's
    flattened aggregates."""
    led = build_memory_ledger(
        _mem(), {"params": 30_000, "opt_state": 15_000}, HLO,
        param_paths={"layers.w_up": 20_000}, capacity_gb=16.0)
    want_waterline = (50_000 + 2_000 + 100_000) / GB
    assert led.measured_source == "accounted"
    assert led.measured_peak_gb == pytest.approx(want_waterline)
    assert led.compiled["waterline_gb"] == pytest.approx(want_waterline)
    assert led.saved_names == ["q_proj", "attn_out"]
    assert led.capacity_gb == 16.0
    led.write(str(tmp_path))
    doc = load_memory_dict(str(tmp_path))
    assert doc["schema"] == ML.MEMORY_SCHEMA_VERSION
    assert doc["measured_source"] == "accounted"
    # memory.json rounds to 9 decimals — compare at that precision
    assert doc["param_paths_gb"]["layers.w_up"] == pytest.approx(
        20_000 / GB, abs=1e-9)
    aggs = memory_aggregates(doc)
    assert aggs["peak"] == pytest.approx(want_waterline, abs=1e-9)
    assert aggs["cat/params"] == pytest.approx(30_000 / GB, abs=1e-9)
    assert aggs["cat/saved_activations"] == pytest.approx(
        FIX_SAVED / GB, abs=1e-9)
    # absent / unreadable → None (mirrors load_ledger_dict)
    assert load_memory_dict(str(tmp_path / "nope")) is None


def test_build_memory_ledger_prefers_allocator_peak():
    s = MemorySampler()
    with s._lock:
        s.samples, s.peak_gb = 4, 1.25
        s.phase_peaks_gb = {"dispatch": 1.25}
    led = build_memory_ledger(_mem(), None, "", sampler=s)
    assert led.measured_source == "allocator"
    assert led.measured_peak_gb == 1.25
    assert led.phase_peaks_gb == {"dispatch": 1.25}
    assert led.samples == 4


def test_join_prediction_accounted_ratio_is_exactly_one():
    led = build_memory_ledger(_mem(), None, HLO)
    v = join_prediction(led, None, strategy="ddp")
    assert v["ok"] and v["violations"] == []
    assert v["compiled_ratio"] == pytest.approx(1.0)
    assert v["compiled_band"] == [0.5, 2.0]
    assert v["measured_source"] == "accounted"
    assert led.prediction_join is v


def test_join_prediction_flags_inflated_measurement():
    led = build_memory_ledger(_mem(), None, "")
    led.measured_peak_gb = led.compiled["waterline_gb"] * 3.0
    led.measured_source = "allocator"
    v = join_prediction(led, None, strategy="ddp")
    assert not v["ok"]
    assert any("outside" in s for s in v["violations"])


def test_join_prediction_judges_planner_band_and_residuals():
    led = build_memory_ledger(
        _mem(), {"params": 30_000, "opt_state": 15_000}, HLO)
    pred = {"predicted_gb": led.measured_peak_gb / 2.0,
            "source": "analytic",
            "components": {"params": 30_000 / GB, "opt": 20_000 / GB,
                           "unknown_term": 1.0}}
    v = join_prediction(led, pred, strategy="fsdp")
    assert v["ok"]
    assert v["predicted_band"] == list(PREDICTION_BANDS["analytic"])
    assert v["predicted_ratio"] == pytest.approx(2.0)
    # residual keys follow measured categories; "opt" aliases opt_state;
    # components the ledger never attributed are skipped
    assert v["residuals"]["params"] == pytest.approx(0.0, abs=1e-6)
    assert v["residuals"]["opt_state"] == pytest.approx(
        (15_000 - 20_000) / GB, abs=1e-6)
    assert "unknown_term" not in v["residuals"]
    # outside the band → violation names the source
    bad = join_prediction(led, {"predicted_gb": led.measured_peak_gb * 9,
                                "source": "analytic"}, strategy="fsdp")
    assert not bad["ok"]
    assert any("analytic" in s for s in bad["violations"])
    # unknown sources fall back to the default band
    v2 = join_prediction(led, {"predicted_gb": led.measured_peak_gb,
                               "source": "crystal_ball"})
    assert v2["predicted_band"] == list(DEFAULT_BAND)


def test_check_memory_regressions_growth_is_the_bad_direction():
    cur = {"peak": 1.3, "cat/params": 0.5, "cat/only_here": 1.0}
    base = {"peak": 1.0, "cat/params": 0.5, "cat/only_there": 1.0}
    recs = {r["key"]: r for r in check_memory_regressions(
        cur, base, max_growth_pct=20.0, label="c", base_label="b")}
    assert recs["peak"]["regressed"]                 # +30 % grows
    assert recs["peak"]["delta_pct"] == pytest.approx(30.0)
    assert not recs["cat/params"]["regressed"]       # flat
    # one-sided keys are skipped, not errors; shrink never regresses
    assert set(recs) == {"peak", "cat/params"}
    assert not check_memory_regressions(
        {"peak": 0.5}, {"peak": 1.0})[0]["regressed"]


# ----------------------------------------- predictor priors round-trip

def test_memory_priors_load_gates_schema(tmp_path):
    from distributed_training_sandbox_tpu.memory_plan import (
        MEMORY_PRIORS_SCHEMA_VERSION, load_memory_priors)
    p = tmp_path / "memory_priors.json"
    p.write_text(json.dumps({
        "schema_version": MEMORY_PRIORS_SCHEMA_VERSION,
        "overall_ratio": 0.5, "n_runs": 3}))
    assert load_memory_priors(str(p))["overall_ratio"] == 0.5
    p.write_text(json.dumps({"schema_version": 99}))
    assert load_memory_priors(str(p)) is None
    assert load_memory_priors(str(tmp_path / "missing.json")) is None


def test_analytic_waterline_recalibrates_from_priors():
    from distributed_training_sandbox_tpu import memory_plan as MP
    from distributed_training_sandbox_tpu.models import transformer as T
    base = MP.analytic_waterline(T.TINY_LM, batch=8, seq=32, ws=8)
    scaled = MP.analytic_waterline(T.TINY_LM, batch=8, seq=32, ws=8,
                                   priors={"overall_ratio": 0.5})
    assert scaled.gb == pytest.approx(base.gb * 0.5)
    assert scaled.components["priors_ratio"] == 0.5
    # garbage ratios are ignored, not fatal
    same = MP.analytic_waterline(T.TINY_LM, batch=8, seq=32, ws=8,
                                 priors={"overall_ratio": "bogus"})
    assert same.gb == pytest.approx(base.gb)


# --------------------------------------------------- synthetic run dirs

def _write_mem_run(root, run_id, peak, *, ok=True, with_memory=True):
    d = root / run_id
    d.mkdir(parents=True)
    verdict = {"strategy": "ddp", "measured_gb": peak,
               "measured_source": "accounted", "compiled_gb": peak,
               "compiled_ratio": 1.0, "compiled_band": [0.5, 2.0],
               "residuals": {}, "ok": ok,
               "violations": [] if ok else ["measured vs compiled: "
                                            "ratio outside (0.5, 2.0)"]}
    man = {"schema": 1, "run_id": run_id, "strategy": "ddp",
           "model": "mlp", "device_count": 8, "platform": "cpu",
           "config": {"num_steps": 4, "batch_size": 8,
                      "sequence_length": 32},
           "contract": {"strategy": "ddp", "ok": True, "violations": []},
           "memory": verdict}
    summ = {"schema": 1, "run_id": run_id, "strategy": "ddp",
            "model": "mlp", "status": "completed", "num_steps": 4,
            "batch_size": 8, "sequence_length": 32,
            "step_time_ms": 10.0, "tokens_per_second": 100.0,
            "memory": verdict}
    (d / "manifest.json").write_text(json.dumps(man))
    (d / "summary.json").write_text(json.dumps(summ))
    if with_memory:
        mem = {"schema": 1,
               "categories_gb": {"params": peak * 0.4,
                                 "opt_state": peak * 0.3,
                                 "activations_workspace": peak * 0.3},
               "param_paths_gb": {}, "phase_peaks_gb": {}, "samples": 0,
               "compiled": {"argument_gb": peak * 0.7,
                            "output_gb": 0.0, "temp_gb": peak * 0.3,
                            "alias_gb": 0.0, "waterline_gb": peak},
               "measured_peak_gb": peak,
               "measured_source": "accounted", "capacity_gb": None,
               "saved_names": [], "prediction_join": verdict}
        (d / MEMORY_FILENAME).write_text(json.dumps(mem))
    return d


# ------------------------------------------------------- lint --memory

def test_lint_memory_mode_exit_codes(tmp_path):
    sys.path.insert(0, str(SCRIPTS))
    from lint_sharding import check_memory_run

    agree = _write_mem_run(tmp_path, "agree-ddp", 1.0, ok=True)
    assert check_memory_run(str(agree)) == 0
    disagree = _write_mem_run(tmp_path, "disagree-ddp", 1.0, ok=False)
    assert check_memory_run(str(disagree)) == 1
    # missing memory.json / missing manifest → exit 2 (inputs absent)
    bare = _write_mem_run(tmp_path, "bare-ddp", 1.0, with_memory=False)
    os.remove(bare / "manifest.json")
    (bare / "manifest.json").write_text(json.dumps(
        {"contract": {"ok": True}}))
    assert check_memory_run(str(bare)) == 2
    assert check_memory_run(str(tmp_path / "nope")) == 2


# --------------------------------------------------- report: the gate

def _report_main():
    sys.path.insert(0, str(SCRIPTS))
    from report import main
    return main


def test_report_gate_fails_on_memory_growth(tmp_path, capsys):
    """THE acceptance gate: --fail-on-memory-regression exits nonzero
    when the measured peak (or any category) grew past the threshold,
    and passes a flat pair."""
    base = tmp_path / "base"
    cur = tmp_path / "cur"
    _write_mem_run(base, "r0-ddp", 1.0)
    _write_mem_run(cur, "r1-ddp", 1.5)             # +50 % peak
    main = _report_main()
    rc = main([str(cur), "--baseline", str(base),
               "--fail-on-memory-regression", "20"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "Memory deltas" in out
    assert "MEMORY REGRESSIONS" in out
    # same pair without the flag: table renders, exit stays 0
    assert main([str(cur), "--baseline", str(base)]) == 0
    # flat pair with the flag: 0
    cur2 = tmp_path / "cur2"
    _write_mem_run(cur2, "r2-ddp", 1.05)
    assert main([str(cur2), "--baseline", str(base),
                 "--fail-on-memory-regression", "20"]) == 0
    # the flag without --baseline is a usage error
    with pytest.raises(SystemExit):
        main([str(cur), "--fail-on-memory-regression", "20"])


def test_report_renders_memory_table(tmp_path, capsys):
    _write_mem_run(tmp_path / "runs", "r0-ddp", 1.0)
    assert _report_main()([str(tmp_path / "runs")]) == 0
    out = capsys.readouterr().out
    assert "Memory ledger (measured vs predicted" in out
    assert "accounted" in out
    assert "▦✓" in out                   # third mark beside ✓ and ⋈


# ----------------------------------------- runs.py: aggregates, priors

def test_runs_registry_memory_aggregates_and_priors(tmp_path):
    sys.path.insert(0, str(SCRIPTS))
    import runs as RR

    conn = RR.connect(str(tmp_path / "runs.sqlite"))
    for i, peak in enumerate([1.0, 1.1, 1.2]):
        RR.index_run_dir(conn, str(_write_mem_run(
            tmp_path, f"r{i}-ddp", peak)))
    rows = conn.execute(
        "SELECT key, gb FROM memory_aggregates WHERE run_id='r0-ddp'"
    ).fetchall()
    assert {r["key"] for r in rows} == {
        "peak", "cat/params", "cat/opt_state",
        "cat/activations_workspace"}
    # diff: growth regresses, direction-aware
    d = RR.diff_runs(conn, "r0-ddp", "r2-ddp")
    assert d["memory"]["peak"]["verdict"] == "regressed"
    assert d["memory"]["peak"]["pct"] == pytest.approx(20.0, abs=0.01)
    assert RR.diff_runs(conn, "r2-ddp", "r0-ddp")[
        "memory"]["peak"]["verdict"] == "improved"
    # priors: median measured/predicted ratio, gated on min_runs
    pri = RR.export_memory_priors(conn)
    assert pri["n_runs"] == 3
    assert pri["overall_ratio"] == pytest.approx(1.0)   # accounted tier
    assert pri["by_strategy"] == {"ddp": 1.0}
    assert pri["by_category"]["params"] == pytest.approx(1.1 * 0.4,
                                                         abs=1e-4)
    with pytest.raises(ValueError):
        RR.export_memory_priors(conn, run_ids=["r0-ddp"], min_runs=3)
    # the exported dict is exactly what the predictor loads
    from distributed_training_sandbox_tpu.memory_plan import (
        load_memory_priors)
    out = tmp_path / "memory_priors.json"
    out.write_text(json.dumps(pri))
    assert load_memory_priors(str(out))["overall_ratio"] == pri[
        "overall_ratio"]


# --------------------------------------- pitfalls: mem-stats-in-hot-loop

def test_pitfall_mem_stats_in_hot_loop_red_green():
    from distributed_training_sandbox_tpu.analysis.pitfalls import (
        lint_source)
    red = (
        "def train_step_loop(devs):\n"
        "    for d in devs:\n"
        "        d.memory_stats()\n")
    hits = [f for f in lint_source(red)
            if f.check == "mem-stats-in-hot-loop"]
    assert len(hits) == 1 and hits[0].severity == "warn"
    # the pragma and the shared sampler are both green
    green_pragma = (
        "def train_step_loop(devs):\n"
        "    for d in devs:\n"
        "        d.memory_stats()  # mem-ok\n")
    assert not [f for f in lint_source(green_pragma)
                if f.check == "mem-stats-in-hot-loop"]
    # outside a *step* function the poll is fine
    green_fn = (
        "def collect_report(devs):\n"
        "    for d in devs:\n"
        "        d.device_memory_stats()\n")
    assert not [f for f in lint_source(green_fn)
                if f.check == "mem-stats-in-hot-loop"]
    # ... and the repo itself must stay clean of the pitfall
    from distributed_training_sandbox_tpu.analysis.pitfalls import (
        lint_tree)
    pkg = Path(__file__).resolve().parent.parent / \
        "distributed_training_sandbox_tpu"
    assert lint_tree(pkg, recursive=True,
                     checks={"mem-stats-in-hot-loop"}) == []


# ----------------------------------- live: predictor band across remat

@pytest.fixture(scope="module")
def fsdp_parts(mesh8):
    import jax
    import jax.numpy as jnp

    from distributed_training_sandbox_tpu.models import transformer as T
    from distributed_training_sandbox_tpu.parallel import fsdp
    params = T.init_params(jax.random.PRNGKey(0), T.TINY_LM)
    shards = fsdp.shard_params_fsdp(params, mesh8)
    opt = fsdp.init_fsdp_opt_state(shards)
    ids = jnp.zeros((8, 32), jnp.int32)
    return shards, opt, (ids, ids)


@pytest.mark.parametrize("policy", ["full", "save_attn", "save_dots"])
def test_live_measured_peak_repins_analytic_band(fsdp_parts, mesh8,
                                                 policy):
    """The predictor re-pin: across remat policies the measured ledger
    peak (accounted tier on CPU) must land inside the analytic band —
    the measured side of test_memory_plan's compile-based pin."""
    import dataclasses

    from distributed_training_sandbox_tpu import memory_plan as MP
    from distributed_training_sandbox_tpu.models import transformer as T
    from distributed_training_sandbox_tpu.parallel import fsdp
    shards, opt, batch = fsdp_parts
    cfg = dataclasses.replace(T.TINY_LM, remat=True, remat_policy=policy)
    step = fsdp.make_fsdp_train_step(shards, cfg, mesh8, donate=False)
    ma = step.lower(shards, opt, batch).compile().memory_analysis()
    mem = {"argument_bytes": ma.argument_size_in_bytes,
           "output_bytes": ma.output_size_in_bytes,
           "temp_bytes": ma.temp_size_in_bytes,
           "alias_bytes": ma.alias_size_in_bytes}
    from distributed_training_sandbox_tpu.utils.memory import (
        tree_size_bytes)
    led = build_memory_ledger(
        mem, {"params": tree_size_bytes(shards),
              "opt_state": tree_size_bytes(opt),
              "batch": tree_size_bytes(batch)},
        param_paths=param_path_bytes(shards))
    pred = MP.analytic_waterline(cfg, batch=8, seq=32, ws=8)
    v = join_prediction(led, {"predicted_gb": pred.gb,
                              "source": "analytic",
                              "components": pred.components},
                        strategy="fsdp")
    assert v["ok"], v["violations"]
    assert v["measured_source"] == "accounted"
    assert v["compiled_ratio"] == pytest.approx(1.0)
    lo, hi = PREDICTION_BANDS["analytic"]
    assert lo < v["predicted_ratio"] < hi


# ------------------------------------- live: the 5-strategy acceptance

LIVE_STRATEGIES = ("ddp", "zero3", "fsdp", "tp", "serve_decode")


@pytest.mark.parametrize("strategy", LIVE_STRATEGIES)
def test_live_memory_ledger_attributes_compiled_step(strategy, tmp_path):
    """Compile the real strategy fixture on the CPU mesh, build the
    memory ledger from its memory_analysis(), and demand a clean
    verdict with attributed categories and the compiled-text parse."""
    from distributed_training_sandbox_tpu.analysis.fixtures import (
        build_strategy)
    from distributed_training_sandbox_tpu.utils.memory import (
        tree_size_bytes)

    b = build_strategy(strategy)
    compiled = b.step.lower(*b.args).compile()
    ma = compiled.memory_analysis()
    mem = {"argument_bytes": ma.argument_size_in_bytes,
           "output_bytes": ma.output_size_in_bytes,
           "temp_bytes": ma.temp_size_in_bytes,
           "alias_bytes": ma.alias_size_in_bytes}
    trees = {"params": tree_size_bytes(b.args[0])}
    if len(b.args) > 1:
        trees["opt_state"] = tree_size_bytes(b.args[1])
    led = build_memory_ledger(mem, trees, compiled.as_text(),
                              param_paths=param_path_bytes(b.args[0]))
    v = join_prediction(led, None, strategy=strategy)
    assert v["ok"], v["violations"]
    assert v["measured_source"] == "accounted"
    assert v["compiled_ratio"] == pytest.approx(1.0)
    assert led.compiled["waterline_gb"] > 0
    assert all(gb >= 0 for gb in led.categories_gb.values())
    assert led.categories_gb["params"] > 0
    assert led.param_paths_gb
    # the artifact round-trips
    led.write(str(tmp_path))
    doc = load_memory_dict(str(tmp_path))
    assert doc["prediction_join"]["ok"]
    assert memory_aggregates(doc)["peak"] == pytest.approx(
        led.measured_peak_gb, abs=1e-9)


# ------------------------------------ live: TelemetryRun end to end

def test_telemetry_run_stamps_memory_verdict(tmp_path, mesh8):
    """The full wire: attach_step_hlo on a profiled run → finalize
    writes memory.json and stamps the MemoryVerdict into manifest.json
    beside the static contract — the third mark."""
    import dataclasses

    from distributed_training_sandbox_tpu import memory_plan as MP
    from distributed_training_sandbox_tpu.models import transformer as T
    from distributed_training_sandbox_tpu.parallel import fsdp
    from distributed_training_sandbox_tpu.telemetry import TelemetryRun

    import jax
    import jax.numpy as jnp
    params = T.init_params(jax.random.PRNGKey(0), T.TINY_LM)
    shards = fsdp.shard_params_fsdp(params, mesh8)
    opt = fsdp.init_fsdp_opt_state(shards)
    ids = jnp.zeros((8, 32), jnp.int32)
    cfg = dataclasses.replace(T.TINY_LM, remat=True, remat_policy="full")
    step = fsdp.make_fsdp_train_step(shards, cfg, mesh8, donate=False)
    pred = MP.analytic_waterline(cfg, batch=8, seq=32, ws=8)

    prof = types.SimpleNamespace(enabled=True, stop=lambda: None,
                                 step=lambda: None, session_dirs=[],
                                 trace_dir=str(tmp_path / "trace"))
    with TelemetryRun("fsdp", mesh=mesh8, results_dir=str(tmp_path),
                      profiler=prof, enabled=True) as telem:
        telem.attach_step_hlo(step, shards, opt, (ids, ids),
                              prediction=pred)
        for _ in range(2):
            telem.step(loss=1.0, tokens=256)

    files = set(os.listdir(telem.run_dir))
    assert MEMORY_FILENAME in files
    doc = load_memory_dict(telem.run_dir)
    assert doc["measured_source"] in ("accounted", "allocator")
    assert doc["categories_gb"]["params"] > 0
    assert doc["categories_gb"]["opt_state"] > 0
    man = json.load(open(os.path.join(telem.run_dir, "manifest.json")))
    assert man["memory"]["ok"], man["memory"]["violations"]
    assert man["memory"]["predicted_source"] == "analytic"
    summ = json.load(open(os.path.join(telem.run_dir, "summary.json")))
    assert summ["memory"]["ok"]
    # runs without an attached step HLO stay memory-silent, not broken
    with TelemetryRun("bare", results_dir=str(tmp_path),
                      enabled=True) as t2:
        t2.step(loss=1.0)
    assert load_memory_dict(t2.run_dir) is None
    man2 = json.load(open(os.path.join(t2.run_dir, "manifest.json")))
    assert man2.get("memory") is None
