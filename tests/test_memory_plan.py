"""Memory planner suite (the ``memplan`` marker, tier-1): waterline
prediction (compile-based == ``memory_analysis()``, compiler-OOM
fallback, analytic ordering across remat policies), auto-fit under a
synthetic tight budget, contracted host offload (bitwise parity on the
CPU fallback + declared-count lint), the shared OOM parser, and the
priors plumbing."""

import dataclasses
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_sandbox_tpu import memory_plan as MP
from distributed_training_sandbox_tpu.models import transformer as T
from distributed_training_sandbox_tpu.parallel import fsdp
from distributed_training_sandbox_tpu.utils.memory import (
    GB, classify_failure, parse_hbm_oom)

pytestmark = pytest.mark.memplan

CFG = T.TINY_LM
OOM_MSG = ("XlaRuntimeError: RESOURCE_EXHAUSTED: Ran out of memory in "
           "memory space hbm. Used 18.41G of 15.75G hbm. Exceeded hbm "
           "capacity by 2.66G.")


# ----------------------------------------------------------- shared parser

def test_parse_hbm_oom_extracts_needed_and_capacity():
    assert parse_hbm_oom(OOM_MSG) == (18.41, 15.75)


def test_parse_hbm_oom_none_on_other_errors():
    assert parse_hbm_oom("ValueError: shapes do not match") is None
    assert parse_hbm_oom("") is None


def test_classify_failure_separates_oom_from_error():
    """What a sweep script's failure row is built from: the OOM's own
    clause, which ``parse_hbm_oom`` reads back, or the error's name."""
    kind, msg = classify_failure(RuntimeError(OOM_MSG))
    assert kind == "oom"
    assert parse_hbm_oom(msg) == (18.41, 15.75)
    assert classify_failure(ValueError("nope")) == ("error",
                                                    "ValueError: nope")


# ------------------------------------------------------------- prediction

@pytest.fixture(scope="module")
def fsdp_setup(mesh8):
    params = T.init_params(jax.random.PRNGKey(0), CFG)
    shards = fsdp.shard_params_fsdp(params, mesh8)
    opt = fsdp.init_fsdp_opt_state(shards)
    ids = jnp.zeros((8, 32), jnp.int32)
    return shards, opt, (ids, ids)


@pytest.mark.parametrize("policy", ["full", "save_attn", "save_dots"])
def test_predict_from_step_matches_memory_analysis(fsdp_setup, mesh8,
                                                   policy):
    """The planner's compile-based prediction IS the compiler's plan:
    args + out + temp − alias from ``memory_analysis()``, exactly."""
    shards, opt, batch = fsdp_setup
    cfg = dataclasses.replace(CFG, remat=True, remat_policy=policy)
    step = fsdp.make_fsdp_train_step(shards, cfg, mesh8, donate=False)
    pred = MP.predict_from_step(step, shards, opt, batch)
    ma = step.lower(shards, opt, batch).compile().memory_analysis()
    want = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes) / GB
    assert pred.source == "memory_analysis"
    assert pred.gb == pytest.approx(want, rel=1e-9)


def test_predict_from_step_compiler_oom_fallback():
    """A compile that dies on XLA's own HBM verdict comes back as a
    prediction, not an exception — the planner's pre-compile reject."""
    boom = types.SimpleNamespace(
        lower=lambda *a: (_ for _ in ()).throw(RuntimeError(OOM_MSG)))
    pred = MP.predict_from_step(boom)
    assert pred.source == "compiler_oom"
    assert pred.fits is False
    assert pred.gb == 18.41
    assert pred.capacity_gb == 15.75


def test_predict_from_step_reraises_non_oom():
    boom = types.SimpleNamespace(
        lower=lambda *a: (_ for _ in ()).throw(ValueError("not memory")))
    with pytest.raises(ValueError):
        MP.predict_from_step(boom)


def test_analytic_orders_remat_policies():
    """More-saving policies must predict more memory, monotonically —
    the knob ordering the planner's search relies on."""
    preds = {}
    for policy in ("full", "save_attn", "save_dots"):
        cfg = dataclasses.replace(T.SMOLLM3_3B_L8, remat_policy=policy)
        preds[policy] = MP.analytic_waterline(cfg, batch=2, seq=8192,
                                              ws=1).gb
    assert preds["full"] < preds["save_attn"] < preds["save_dots"]


def test_analytic_vs_compiled_same_ballpark(fsdp_setup, mesh8):
    """CPU-mesh agreement: the analytic walk and the compiler's plan for
    the same tiny step agree within a small factor (CPU XLA pads and
    fuses differently than the TPU model the analytics target — the
    tight ~10% calibration is against the TPU verdicts, RESULTS.md)."""
    shards, opt, batch = fsdp_setup
    step = fsdp.make_fsdp_train_step(shards, CFG, mesh8, donate=False)
    compiled = MP.predict_from_step(step, shards, opt, batch)
    analytic = MP.analytic_waterline(CFG, batch=8, seq=32, ws=8)
    assert compiled.gb > 0 and analytic.gb > 0
    assert 0.2 < analytic.gb / compiled.gb < 5.0


def test_analytic_tracks_bench_r05_oom_verdicts():
    """Re-read a v5e's recorded OOM wall through the predictor: each
    compiler-reported used-HBM verdict is matched within the calibrated
    band (±20%; the measured mean is ~6%, RESULTS.md).  The verdicts were
    recorded while the layer's recomputation still re-ran the splash
    kernel, so the residuals the step keeps since are taken off the
    prediction before it is held to them."""
    rows = [
        ({"remat_policy": "save_dots_q8", "matmul_precision": "int8_bwd"},
         "full", 4, 18.41),
        ({"matmul_precision": "int8_bwd"}, "int8", 16, 19.86),
        ({"remat_policy": "save_dots", "matmul_precision": "int8_bwd"},
         "int8", 2, 18.20),
        ({"remat_policy": "save_dots_q8", "matmul_precision": "int8_bwd"},
         "int8", 4, 16.82),
    ]
    for over, state, batch, measured in rows:
        cfg = dataclasses.replace(T.SMOLLM3_3B_L8, **over)
        pred = MP.analytic_waterline(cfg, batch=batch, seq=8192, ws=1,
                                     state_precision=state)
        kept = 8 * batch * 8192 * 16 * (128 * 2 + 4) / GB
        assert pred.gb - kept == pytest.approx(measured, rel=0.20), \
            f"{over} s={state} b={batch}: {pred.gb - kept:.2f} vs {measured}"


# ---------------------------------------------------------------- planner

def test_auto_fit_picks_fitting_config_under_tight_budget():
    """Synthetic tight budget between the smallest and largest predicted
    waterlines: the planner must reject the over-budget region
    pre-compile (source stays analytic) and choose a fitting config."""
    cfg = T.SMOLLM3_3B_L8
    all_preds = [
        MP.analytic_waterline(c.apply_to(cfg), batch=8, seq=8192, ws=1,
                              accum_steps=c.accum_steps,
                              state_precision=c.state_precision,
                              offload=c.offload).gb
        for c in MP.enumerate_candidates(per_device_batch=8)]
    budget = (min(all_preds) + max(all_preds)) / 2
    plan = MP.plan(cfg, batch=8, seq=8192, ws=1, hbm_budget_gb=budget)
    assert plan.best is not None
    assert plan.best.prediction.gb <= budget
    assert plan.best.prediction.source == "analytic"
    rejected = [r for r in plan.rows if not r.fits]
    assert rejected, "a mid-range budget must reject something"
    for r in rejected:
        assert r.prediction.gb > budget      # rejected WITH a waterline
        assert r.prediction.source == "analytic"   # … and pre-compile
    assert "chose" in plan.summary()


def test_auto_fit_prefers_faster_fitting_config():
    """Among fitting candidates the modeled-throughput ranking decides:
    int8_bwd outranks bf16 at the same remat policy."""
    plan = MP.plan(T.SMOLLM3_3B_L8, batch=2, seq=8192, ws=1,
                   hbm_budget_gb=1000.0)
    assert plan.best.candidate.matmul_precision == "int8_bwd"


def test_no_fitting_config_raises_with_waterline():
    with pytest.raises(MP.NoFittingConfig) as ei:
        MP.plan(T.SMOLLM3_3B_L8, batch=64, seq=8192, ws=1,
                hbm_budget_gb=1.0)
    assert "1.00 GB" in str(ei.value)
    assert ei.value.plan.rows            # every candidate priced anyway


def test_verify_hook_demotes_compiler_oom():
    """The compile-side re-check overrules an analytic fit: the head
    candidate's step OOMs at compile → runner-up is promoted."""
    ma = types.SimpleNamespace(argument_size_in_bytes=GB,
                               output_size_in_bytes=0,
                               temp_size_in_bytes=GB,
                               alias_size_in_bytes=0)
    ok_step = types.SimpleNamespace(lower=lambda *a: types.SimpleNamespace(
        compile=lambda: types.SimpleNamespace(memory_analysis=lambda: ma)))
    boom = types.SimpleNamespace(
        lower=lambda *a: (_ for _ in ()).throw(RuntimeError(OOM_MSG)))
    cands = [MP.Candidate(remat_policy="full"),
             MP.Candidate(remat_policy="save_attn")]

    def verify(c):
        # save_attn ranks first (faster model); make it OOM compile-side
        return (boom if c.remat_policy == "save_attn" else ok_step), ()

    plan = MP.plan(T.SMOLLM3_3B_L8, batch=2, seq=8192, ws=1,
                   hbm_budget_gb=1000.0, candidates=cands, verify=verify)
    assert plan.best.candidate.remat_policy == "full"
    assert plan.best.prediction.source == "memory_analysis"
    oomed = [r for r in plan.rows
             if r.candidate.remat_policy == "save_attn"][0]
    assert oomed.fits is False
    assert oomed.prediction.source == "compiler_oom"


def test_enumerate_prunes_indivisible_accum():
    cands = MP.enumerate_candidates(per_device_batch=2, accum=(1, 2, 4))
    assert all(c.accum_steps in (1, 2) for c in cands)


def test_parse_bench_config_name():
    assert MP.parse_bench_config_name("explicit_reshard") == {
        "remat_policy": "full", "matmul_precision": "bf16",
        "state_precision": "full", "batch_scale": 1}
    assert MP.parse_bench_config_name("explicit_save_dots_q8_int8_b2x") \
        == {"remat_policy": "save_dots_q8",
            "matmul_precision": "int8_bwd",
            "state_precision": "full", "batch_scale": 2}
    assert MP.parse_bench_config_name("explicit_int8_bwd_s8_b4x") == {
        "remat_policy": "full", "matmul_precision": "int8_bwd",
        "state_precision": "int8", "batch_scale": 4}
    assert MP.parse_bench_config_name("auto_int8") is None
    assert MP.parse_bench_config_name("explicit_ring") is None
    assert MP.parse_bench_config_name(
        "explicit_reshard_syncstep") is None


def test_bench_priors_anchor_modeled_speed(tmp_path, monkeypatch):
    """A measured bench row with matching knobs anchors the score
    directly (its TFLOPS), beating the multiplier model's guess; a file
    nobody names is not read, wherever the process stands."""
    rows = {"matrix": [
        {"config": "explicit_int8_bwd_b4x", "tflops_per_device": 125.7,
         "step_ms": 3100.0, "batch": 8},
        {"config": "explicit_save_dots_q8_int8_b2x",
         "error": "OOM"},                      # error rows filtered out
    ]}
    p = tmp_path / "BENCH_x.json"
    p.write_text(json.dumps(rows))
    monkeypatch.chdir(tmp_path)
    assert MP.load_bench_priors() == []
    priors = MP.load_bench_priors([str(p)])
    assert len(priors) == 1
    assert priors[0]["knobs"]["matmul_precision"] == "int8_bwd"
    plan = MP.plan(T.SMOLLM3_3B_L8, batch=8, seq=8192, ws=1,
                   hbm_budget_gb=1000.0, priors=priors,
                   prior_base_batch=2)
    anchored = [r for r in plan.rows if r.prior]
    assert anchored
    exact = [r for r in anchored if r.candidate.offload == "none"
             and r.candidate.accum_steps == 1]
    assert all(r.score == pytest.approx(125.7) for r in exact)
    # offload/accum never appear in bench names: their cost still
    # discounts an anchored score (no free ride on the tie-break)
    offloaded = [r for r in anchored if r.candidate.offload == "opt"
                 and r.candidate.accum_steps == 1]
    assert all(r.score == pytest.approx(125.7 * 0.97) for r in offloaded)


# ----------------------------------------------------------- host offload

def test_offload_opt_parity_losses_bitwise(mesh8):
    """--offload opt must not change a single bit of the training math:
    where the backend has a pinned_host space the moments stream through
    real transfers; on the CPU mesh the fallback build is transfer-free.
    Either way the loss sequence is bitwise-identical to no-offload."""
    params = T.init_params(jax.random.PRNGKey(1), CFG)
    ids = jax.random.randint(jax.random.PRNGKey(2), (8, 32), 0,
                             CFG.vocab_size)
    batch = (ids, ids)
    losses = {}
    for mode in ("none", "opt"):
        shards = fsdp.shard_params_fsdp(
            T.init_params(jax.random.PRNGKey(1), CFG), mesh8)
        opt = fsdp.init_fsdp_opt_state(shards)
        if mode == "opt" and MP.supports_host_offload():
            opt = MP.offload_tree(opt)
        step = fsdp.make_fsdp_train_step(shards, CFG, mesh8, offload=mode,
                                         donate=False)
        seq = []
        for _ in range(3):
            shards, opt, loss = step(shards, opt, batch)
            seq.append(np.asarray(loss))
        losses[mode] = np.stack(seq)
    np.testing.assert_array_equal(losses["none"], losses["opt"])
    del params


def test_offload_plan_declares_counts_by_support(mesh8):
    opt = fsdp.init_fsdp_opt_state(fsdp.shard_params_fsdp(
        T.init_params(jax.random.PRNGKey(0), CFG), mesh8))
    supported = MP.plan_offload("opt", opt, supported=True)
    assert supported.n_state_leaves == 22          # mu + nu leaves
    counts = supported.host_transfer_counts()
    assert counts["move_to_host"][0] >= 1
    assert counts["move_to_host"][1] == 44
    fallback = MP.plan_offload("opt", opt, supported=False)
    assert fallback.host_transfer_counts() == {}
    assert MP.plan_offload("none").host_transfer_counts() == {}
    with pytest.raises(ValueError):
        MP.plan_offload("everything")


def test_offload_fallback_step_is_transfer_free(mesh8):
    """Contract-count fallback where the backend has no host memory
    kinds: the offload step's lowered HLO must carry zero transfer
    markers — exactly what the empty declaration makes the lint
    enforce."""
    if MP.supports_host_offload():
        pytest.skip("backend has pinned_host; the real-transfer leg of "
                    "test_offload_opt_parity covers it")
    shards = fsdp.shard_params_fsdp(
        T.init_params(jax.random.PRNGKey(0), CFG), mesh8)
    opt = fsdp.init_fsdp_opt_state(shards)
    step = fsdp.make_fsdp_train_step(shards, CFG, mesh8, offload="opt",
                                     donate=False)
    ids = jnp.zeros((8, 32), jnp.int32)
    text = step.lower(shards, opt, (ids, ids)).as_text()
    assert "MoveToHost" not in text
    assert "MoveToDevice" not in text


def test_fsdp_step_rejects_unknown_offload(mesh8):
    shards = fsdp.shard_params_fsdp(
        T.init_params(jax.random.PRNGKey(0), CFG), mesh8)
    with pytest.raises(ValueError, match="offload"):
        fsdp.make_fsdp_train_step(shards, CFG, mesh8, offload="hbm2")


def test_offload_activations_needs_named_policy():
    with pytest.raises(ValueError, match="offload_activations"):
        dataclasses.replace(CFG, remat=True, remat_policy="full",
                            offload_activations=True)
    cfg = dataclasses.replace(CFG, remat=True, remat_policy="save_attn",
                              offload_activations=True)
    assert T.resolve_remat_policy(cfg) is not None


# --------------------------------------------------- offload-aware lint

_TRANSFER_HLO = """
HloModule step
  mth1 = f32[8]{0} custom-call(x), custom_call_target="MoveToHost"
  mth2 = f32[8]{0} custom-call(y), custom_call_target="MoveToHost"
  mtd1 = f32[8]{0} custom-call(a), custom_call_target="MoveToDevice"
  mtd2 = f32[8]{0} custom-call(b), custom_call_target="MoveToDevice"
"""


def test_lint_undeclared_move_to_host_stays_red():
    """Seeded violation: host transfers with NO offload declaration are
    hot-path errors, exactly as before the planner existed."""
    from distributed_training_sandbox_tpu.analysis.hlo_lint import (
        check_host_transfers)
    findings = check_host_transfers(_TRANSFER_HLO)
    assert findings
    assert all(f.check == "host_transfer" and f.severity == "error"
               for f in findings)


def test_lint_declared_transfers_allowed_and_count_checked():
    from distributed_training_sandbox_tpu.analysis.hlo_lint import (
        check_host_transfers)
    ok = check_host_transfers(
        _TRANSFER_HLO, declared={"move_to_host": (1, 4),
                                 "move_to_device": (1, 4)})
    assert ok == []
    wrong = check_host_transfers(
        _TRANSFER_HLO, declared={"move_to_host": (3, 8),
                                 "move_to_device": (1, 4)})
    assert len(wrong) == 1
    assert "2 transfer site(s)" in wrong[0].message
    # empty declaration (unsupported-backend fallback): strict forbid
    fallback = check_host_transfers(_TRANSFER_HLO, declared={})
    assert fallback
    clean = check_host_transfers("HloModule step", declared={})
    assert clean == []


def test_fsdp_offload_contract_reads_plan_from_ctx():
    from distributed_training_sandbox_tpu.analysis.contracts import (
        CONTRACTS, ContractContext)
    contract = CONTRACTS["fsdp_offload"]
    on = ContractContext(extra={"offload": {
        "mode": "opt", "supported": True, "n_state_leaves": 22,
        "state_bytes": 0, "act_names": []}})
    declared = contract.host_transfers(on)
    assert declared["move_to_device"] == (1, 44)
    off = ContractContext(extra={"offload": {
        "mode": "opt", "supported": False, "n_state_leaves": 22}})
    assert contract.host_transfers(off) == {}


def test_lint_cli_passes_fsdp_offload_fixture(tmp_path):
    """scripts/lint_sharding.py end-to-end on the offload fixture: the
    offload-aware contract + declared-transfer lint must come back
    clean (the CI gate the satellite asks for)."""
    from scripts.lint_sharding import main
    out = tmp_path / "r.json"
    rc = main(["--cpu-devices", "0", "--strategies", "fsdp_offload",
               "--skip-recompile", "--skip-scripts", "--json", str(out)])
    assert rc == 0
    rep = json.loads(out.read_text())["strategies"]["fsdp_offload"]
    assert rep["contract"]["ok"] is True
    assert rep["lint"] == []


# -------------------------------------------------------- config & report

def test_trainconfig_memory_plan_flags():
    from distributed_training_sandbox_tpu.utils import TrainConfig
    cfg = TrainConfig.from_args(["--offload", "opt", "--auto-fit",
                                 "--hbm-budget-gb", "14.5"])
    assert cfg.offload == "opt"
    assert cfg.auto_fit is True
    assert cfg.hbm_budget_gb == 14.5
    dflt = TrainConfig.from_args([])
    assert dflt.offload == "none" and dflt.auto_fit is False
    assert dflt.hbm_budget_gb is None


def test_report_table_memory_column(tmp_path):
    from distributed_training_sandbox_tpu.telemetry import report as R
    d = tmp_path / "20260804-000000-fsdp"
    d.mkdir()
    (d / "manifest.json").write_text(json.dumps({
        "run_id": "r1", "strategy": "fsdp", "device_count": 8,
        "extra": {"memory_plan": {"predicted_gb": 12.34,
                                  "compiled_gb": 13.5,
                                  "budget_gb": 15.75}}}))
    (d / "summary.json").write_text(json.dumps({
        "run_id": "r1", "strategy": "fsdp", "status": "completed"}))
    rows = [R.run_row(rec) for rec in R.discover_runs([str(tmp_path)])]
    assert rows[0]["predicted_gb"] == 12.34
    assert rows[0]["compiled_gb"] == 13.5
    table = R.render_table(rows)
    assert "mem GB" in table
    assert "13.50/15.8" in table
    # predicted-only runs render with the ~ prefix
    del rows[0]["compiled_gb"]
    assert "~12.34/15.8" in R.render_table(rows)


@pytest.mark.parametrize("policy", ["full", "save_attn", "save_dots",
                                    "save_dots_q8"])
def test_analytic_counts_the_flash_residuals_under_every_policy(policy):
    """Under the splash kernel a rematerialised step keeps the kernel's
    output and its fp32 log-sum-exp a layer whatever the policy
    (``resolve_remat_policy``): the waterline's saved term is the
    einsum path's plus ``L·micro·seq·n_q·(hd·itemsize + 4)``; without
    remat everything lives already and nothing is added."""
    flash = dataclasses.replace(T.SMOLLM3_3B_L8, remat_policy=policy)
    assert flash.attention_impl == "flash" and flash.remat
    xla = dataclasses.replace(flash, attention_impl="xla")
    saved = lambda cfg, **kw: MP.analytic_waterline(  # noqa: E731
        cfg, batch=4, seq=8192, ws=1, **kw).components["saved_activations"]
    kept = 8 * 4 * 8192 * 16 * (128 * 2 + 4) / GB
    assert saved(flash) - saved(xla) == pytest.approx(kept, rel=1e-9)
    # four chips hold a quarter of the rows each
    per_chip = lambda cfg: MP.analytic_waterline(  # noqa: E731
        cfg, batch=4, seq=8192, ws=4).components["saved_activations"]
    assert per_chip(flash) - per_chip(xla) == pytest.approx(kept / 4,
                                                            rel=1e-9)
    no_remat = dataclasses.replace(flash, remat=False)
    assert saved(no_remat) == saved(
        dataclasses.replace(no_remat, attention_impl="xla"))
    if policy in ("save_attn", "save_dots_q8"):
        # the named tensors park on the host; the kernel's stay
        assert saved(flash, offload="opt_act") == pytest.approx(kept,
                                                                rel=1e-9)


@pytest.mark.parametrize("seq,micro", [(2048, 16), (8192, 4), (32768, 1)])
def test_analytic_counts_the_fused_backwards_dq_partials(seq, micro):
    """The splash kernel's fused backward writes one dq partial a KV
    block, each of q's shape, beside q, k, v, the output, its cotangent
    and their three gradients: ``micro·seq·hd·itemsize·(4·(n_q + n_kv) +
    (seq / block_kv)·n_q)`` at the KV block the kernel's own rule gives
    that window.  A layer's working set is that phase where it is larger
    than the projections' (a narrow MLP), and the projections' where it
    is not (the flagship's widths, at every window of the cells)."""
    working = lambda cfg: MP.analytic_waterline(  # noqa: E731
        cfg, batch=micro, seq=seq, ws=1).components["layer_working"]
    scores = micro * 16 * seq * seq * 4 / GB    # the plain path's own
    plain = lambda cfg: working(dataclasses.replace(  # noqa: E731
        cfg, attention_impl="xla")) - scores
    wide = T.SMOLLM3_3B_L8
    assert wide.attention_impl == "flash"
    _, block_kv, _ = T.flash_backward_blocks(seq)
    phase = micro * seq * 128 * 2 * (4 * (16 + 4)
                                     + (seq // block_kv) * 16) / GB
    assert plain(wide) > phase and working(wide) == plain(wide)
    narrow = dataclasses.replace(wide, intermediate_size=2048)
    assert plain(narrow) < phase
    assert working(narrow) == pytest.approx(phase, rel=1e-9)
