"""The ``mla_moe`` architecture's benchmark files: its counts pinned to the
arithmetic of the cut, its configuration and traffic files, the subscope
helper on a hand-made trace, the four readers built on it, and the new
cell's rehearsal.  CPU only: counts and control flow, no device metric."""

import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import harness  # noqa: E402
from benchmarks.layer_metrics import _scopes as S  # noqa: E402
from benchmarks.layer_metrics import _subscopes as SS  # noqa: E402

CELL = "serve-mla-moe-longgen"
CONFIG = ROOT / "benchmarks/configs/pangu-ultra-moe-ep32-serve.json"
TRAFFIC = ROOT / "benchmarks/workloads/reasoning-backlog.json"
#: openPangu-Ultra-MoE-718B's published config.json, the numbers
PUBLISHED = {
    "first_k_dense_replace": 3, "hidden_size": 7680,
    "intermediate_size": 18432, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "moe_intermediate_size": 2048,
    "n_routed_experts": 256, "n_shared_experts": 1,
    "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 128,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_theta": 25600000, "routed_scaling_factor": 2.5, "v_head_dim": 128,
    "vocab_size": 153600}
REDUCED = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
           "n_routed_experts": 8}


@pytest.fixture(scope="module")
def cfg_file():
    return json.loads(CONFIG.read_text())


@pytest.fixture(scope="module")
def counts():
    return harness.find_module("counts", "mla_moe")


# ------------------------------------------------------------- the counts

def test_counts_are_the_cuts_arithmetic(cfg_file, counts):
    f = cfg_file["fields"]
    # per layer outside the routed experts: 11.80 + 37.75 + 4.42 + 16.78
    # + 125.83 M of latent projections
    assert counts.attention_weight_count(f) == (
        7680 * 1536 + 1536 * 128 * 192 + 7680 * 576 + 512 * 128 * 256
        + 128 * 128 * 7680) == 196_575_232
    assert counts.expert_weight_count(f) == 3 * 7680 * 2048 == 47_185_920
    norms = 4 * 7680 + 1536 + 512
    dense = 196_575_232 + norms + 3 * 7680 * 18432
    expert = 196_575_232 + norms + 7680 * 256 + 9 * 47_185_920
    want = dense + 4 * expert + 2 * 153600 * 7680 + 7680
    assert counts.param_count(f) == want == 5_473_574_400
    assert round(2 * want / 1e9, 2) == 10.95              # GB of bf16
    assert counts.kv_bytes_per_token(f) == 5 * 1152       # 576 bf16 a layer
    live = 64 * 1600.0
    # every weight but the embedding table, plus the live latent rows
    assert counts.decode_step_bytes(f, live) == pytest.approx(
        2 * (want - 153600 * 7680) + live * 5760)
    assert counts.decode_step_bytes(f, live, experts_touched=28) \
        == pytest.approx(counts.decode_step_bytes(f, live)
                         - 4 * 2 * 47_185_920)
    assert counts.latent_decode_attention_flops(f, live) \
        == 5 * live * 2 * 128 * (576 + 512)
    assert counts.latent_decode_attention_bytes(f, live, 64) \
        == 5 * (live * 1152 + 64 * (128 * 576 * 2 + 128 * 512 * 4))
    assert counts.expert_step_bytes(f, 28) == 28 * 47_185_920 * 2
    # the v5e's ridge: 242 FLOP a byte against 197e12 / 819e9 = 240
    assert 2 * 128 * 1088 / 1152 == pytest.approx(241.8, abs=0.1)


def test_counts_are_the_programs_own(cfg_file, counts):
    import jax
    from distributed_training_sandbox_tpu.models import transformer as T
    mcfg = harness.model_config(cfg_file["fields"])
    assert mcfg.param_count() == counts.param_count(cfg_file["fields"])
    shapes = jax.eval_shape(lambda k: T.init_params(k, mcfg),
                            jax.random.key(0))
    import math
    assert sum(math.prod(x.shape) for x in jax.tree.leaves(shapes)) \
        == counts.param_count(cfg_file["fields"])
    tiny = {**cfg_file["fields"], **cfg_file["rehearse"]["fields"]}
    assert harness.model_config(tiny).param_count() \
        == counts.param_count(tiny)


# ---------------------------------------------------------- the data files

def test_config_file_states_the_cut_and_keeps_every_published_width(
        cfg_file):
    f, fields = cfg_file, cfg_file["fields"]
    assert {k: v for k, v in f["published"].items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)} \
        == PUBLISHED
    assert sorted(f["reduced"]) == sorted(REDUCED)
    for k, v in PUBLISHED.items():
        # the top level of the file is the published config AS RUN
        assert f[k] == REDUCED.get(k, v), k
        if k in fields:
            assert fields[k] == REDUCED.get(k, v), k
    assert fields["router_width"] == PUBLISHED["n_routed_experts"]
    assert fields["expert_offset"] == 0
    assert fields["sandwich_norm"] is True is f["published"]["sandwich_norm"]
    assert fields["norm_topk_prob"] is True
    assert fields["tie_word_embeddings"] is False
    assert f["architecture"] == "mla_moe" and f["runner"] == "serve"
    assert f["deployment"]["chips_sharing_a_layer"] == 32
    assert "rank 0" in f["deployment"]["layout"]
    assert {"rope", "router", "weights"} <= set(f["assumed"])
    assert "multi-token-prediction" in f["not_run"]
    assert set(f["check"]) == {"gap_sigma_mean", "gap_sigma_max", "why"}
    assert f["serve"]["engine"] == {}       # every other argument: default


def test_traffic_file_carries_the_issues_parameters():
    t = json.loads(TRAFFIC.read_text())
    assert t["generator"] == "request_stream"
    assert t["params"] == {
        "arrival": {"process": "backlog", "count": 320},
        "prompt_len": {"dist": "lognormal", "median": 768, "sigma": 0.5,
                       "min": 256, "max": 2048, "stratified": 8},
        "output_len": {"dist": "uniform", "min": 384, "max": 768,
                       "stratified": 8},
        "max_total": 2816}
    assert t["engine"] == {"max_batch": 64, "max_seq_len": 4096,
                           "page_size": 16, "prefill_chunk": 256}
    assert t["drain_s"] == 30.0 and t["trace"] == {"seconds": 6.0}
    assert t["check"]["requests"] == 2


def test_the_cell_reports_the_shared_readers_and_its_own_four():
    cell = harness.load_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "pangu-ultra-moe-ep32-serve", "reasoning-backlog", 1)
    assert [m.name for m in cell.end_to_end] == ["serve_tokens_per_s"]
    assert {m.name for m in cell.per_layer} >= {
        "decode_ms_per_step_tput", "prefill_ms_per_chunk_tput",
        "decode_attn_ms_tput", "prefill_attn_ms_tput",
        "engine_batch_occupancy_tput", "sched_host_ms_per_round_tput",
        "serve_device_idle_pct_tput", "decode_inplace_share_tput",
        "mla_decode_attn_roofline_tput", "moe_experts_ms_tput",
        "moe_experts_roofline_tput", "moe_tokens_per_expert_tput"}
    counts = harness.cell_counts(cell)
    assert Path(counts.__file__).name == "mla_moe.py"
    # the older serving cells do not report the new four
    doc = harness.load_cell("serve-doc-batch")
    assert not any(m.name.startswith(("moe_", "mla_"))
                   for m in doc.per_layer)


# ------------------------------------------------------------ the subscopes

def test_subscope_names_are_the_programs():
    from distributed_training_sandbox_tpu.utils import profiling
    assert SS.SUBSCOPES == profiling.SUBSCOPES
    assert not set(SS.SUBSCOPES) & set(S.CATALOGUE)


@pytest.mark.parametrize("path,want", [
    ("jit(<unknown>)/mlp/moe_experts/dot_general", "moe_experts"),
    ("jit(<unknown>)/mlp/moe_route/top_k", "moe_route"),
    ("jit(<unknown>)/mlp/moe_shared/jit(silu)/logistic", "moe_shared"),
    ("jit(<unknown>)/mlp/dot_general", None),
    ("jit(<unknown>)/mlp/remoe_experts_x/add", None),       # whole words
    ("", None), (None, None)])
def test_innermost_subscope(path, want):
    assert SS.innermost(path) == want
    if want:        # the catalogue's reader still books it to ``mlp``
        assert S.innermost(path) == "mlp"


def test_subscope_self_time_per_program_on_a_small_trace():
    us = 1e3
    decode, prefill = "jit__unknown(7)", "jit__unknown(9)"
    ops = [
        # decode launch 0..400: a fusion of 100 under moe_experts that
        # nests a 30 op of moe_route; the experts' self time is 70
        ("fusion.1", 10 * us, 100 * us, "jit(<unknown>)/mlp/moe_experts/dot"),
        ("copy.2", 20 * us, 30 * us, "jit(<unknown>)/mlp/moe_route/top_k"),
        ("fusion.3", 200 * us, 50 * us, "jit(<unknown>)/mlp/moe_shared/dot"),
        ("fusion.4", 300 * us, 40 * us, "jit(<unknown>)/mlp/dot_general"),
        # prefill launch 500..900, cut by the window's end at 700
        ("fusion.5", 650 * us, 100 * us, "jit(<unknown>)/mlp/moe_experts/x"),
        ("while.6", 500 * us, 400 * us, "jit(<unknown>)/mlp/moe_experts"),
    ]
    raw = S.ScopedRaw(devices={"/device:TPU:0": {
        "ops": ops, "modules": [(decode, 0.0, 400 * us),
                                (prefill, 500 * us, 400 * us)]}})
    got = SS.reduce(raw, (0.0, 700 * us))
    assert got == pytest.approx({
        (decode, "moe_experts"): 70 * us, (decode, "moe_route"): 30 * us,
        (decode, "moe_shared"): 50 * us,
        (prefill, "moe_experts"): 50 * us})     # the while is a container


# -------------------------------------------------------------- the readers

def _ctx(counts, fields, stats, **counters):
    return SimpleNamespace(
        trace=None, fields=fields, counts=counts,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        counters={"stats": stats, "engine": {"max_batch": 64}, **counters})


@pytest.mark.parametrize("name", [
    "mla_decode_attn_roofline_tput", "moe_experts_ms_tput",
    "moe_experts_roofline_tput", "moe_tokens_per_expert_tput"])
def test_a_program_without_the_names_gives_the_new_readers_nothing(
        name, cfg_file, counts):
    """The parent's engine has neither the subscopes nor the counters, and
    an untraced run has no table: each reader returns None, never raises."""
    mod = harness.find_module("layer_metrics", name)
    old = {"rounds": 9, "decode_steps": 36, "occupancy_sum": 50}
    assert mod.read(_ctx(counts, cfg_file["fields"], old, kv_valid_sum=9,
                         kv_samples=3)) is None
    assert (mod.LAYER, mod.MOVES, mod.RUNNERS) == (
        {"mla_decode_attn_roofline_tput": "kernels",
         "moe_experts_ms_tput": "model step",
         "moe_experts_roofline_tput": "kernels",
         "moe_tokens_per_expert_tput": "scheduler"}[name],
        "serve_tokens_per_s", ("serve",))


def test_the_new_readers_arithmetic(monkeypatch, cfg_file, counts):
    f = cfg_file["fields"]
    stats = {"rounds": 10, "decode_steps": 40, "occupancy_sum": 640,
             "moe_assignments": 40 * 4 * 64 * 8,
             "moe_assignments_held": 2560, "moe_experts_touched": 1120,
             "moe_expert_layer_steps": 160}
    ctx = _ctx(counts, f, stats, kv_valid_sum=10 * 64 * 1600, kv_samples=10)
    tok = harness.find_module("layer_metrics", "moe_tokens_per_expert_tput")
    assert tok.read(ctx) == pytest.approx(2560 / 1120)
    ms = harness.find_module("layer_metrics", "moe_experts_ms_tput")
    monkeypatch.setattr(ms._subscopes, "subscope_ms_per_launch",
                        lambda ctx, names, label: 5.0
                        if (names, label) == (("moe_experts",), "decode")
                        else None)
    assert ms.read(ctx) == 5.0
    roof = harness.find_module("layer_metrics", "moe_experts_roofline_tput")
    # 28 experts touched a step x 94.4 MB at 819 GB/s = 3.23 ms of 5
    assert roof.read(ctx) == pytest.approx(
        100 * 28 * 47_185_920 * 2 / 819e9 / 5e-3)
    attn = harness.find_module("layer_metrics",
                               "mla_decode_attn_roofline_tput")
    monkeypatch.setattr(attn._scopes, "scope_ms_per_launch",
                        lambda ctx, scopes, label: 2.0
                        if (scopes, label) == (("attn_core",), "decode")
                        else None)
    live = 64 * 1600.0
    least = max(5 * live * 2 * 128 * 1088 / 197e12,
                5 * (live * 1152 + 64 * (128 * 576 * 2 + 128 * 512 * 4))
                / 819e9)
    assert attn.read(ctx) == pytest.approx(100 * least / 2e-3)
    assert 30 < attn.read(ctx) < 50


# ------------------------------------------------------------ the rehearsal

def test_the_new_cells_rehearsal_passes():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks/run.py"), "--workload", CELL,
         "--rehearse-cpu"], capture_output=True, text=True, timeout=600,
        cwd=str(ROOT), env=env)
    assert out.returncode == 0, out.stdout + out.stderr
    assert f"cell={CELL}" in out.stdout
    assert "failed=0 reference_ok=True compiles_in_window=0" in out.stdout
    check = json.loads(out.stdout.split("rehearsal check: ", 1)[1])
    assert check["reference"] == "benchmarks/reference/mla_moe.py"
    assert check["retraces_after_warmup"] == 0
    assert check["tokens_checked"] > 0


# ------------------------------------------------- the check separates faults

def _drive(check, fault=None, fields=None, engine=None):
    """A rehearsal of the cell in this process (the harness's look for a
    chip skipped), held to ``check``; returns the runner's observation."""
    import contextlib
    import time
    cell = harness.load_cell(CELL)
    cell.config["check"].update(check)
    # sharper attention and closer logits than the cell's own scale: at the
    # rehearsal's 64-wide model a fault has few tokens to show in
    cell.config["serve"]["param_scale"] = 3.0
    cell.config["rehearse"]["fields"].update(fields or {})
    cell.config["serve"]["engine"].update(engine or {})
    runner = harness.find_module("runners", cell.runner)
    ref = harness.find_module("reference", cell.architecture,
                              needs=runner.REFERENCE_EXPORTS)
    with fault() if fault else contextlib.nullcontext():
        obs = runner.run(cell, ref=ref, seed=11, seconds=2.0, trace=False,
                         rehearse=True, watch=harness.CompileWatch(),
                         phases=harness.Phases(time.perf_counter()))
    assert obs["attempted"] > 0 and obs["failed"] == 0
    return obs


#: the rehearsal computes in float32, where the sound program's served
#: token is the reference's argmax (gap 0 at every position)
TIGHT = {"gap_sigma_mean": 0.002, "gap_sigma_max": 0.1}


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernel"])
def test_the_sound_program_is_correct_under_tight_limits(kernel):
    obs = _drive(TIGHT, engine={"paged_kernel": kernel})
    assert obs["correct"], obs["check"]
    assert obs["counters"]["stats"]["moe_assignments"] > 0
    assert (obs["counters"]["stats"]["decode_inplace_steps"] > 0) == kernel


@pytest.mark.parametrize("fault", [
    "skip_shared_expert", "renormalise_over_held",
    "scores_without_the_rope_columns"])
def test_a_planted_fault_in_a_decode_step_is_not_correct(fault):
    """Each fault of ``mla_moe_faults`` moves served tokens off the
    reference's argmax by more than the tight limits allow, with nothing
    else failing: no request is lost, nothing recompiles.  (The fourth
    fault of the chip's list, int8 projections, moves no token of a 64-wide
    float32 model: gap 0.02; its chip readings are in the configuration's
    ``check.why``.)"""
    from tests.benchmark import mla_moe_faults
    obs = _drive(TIGHT, mla_moe_faults.FAULTS[fault],
                 engine={"paged_kernel": True})
    check = obs["check"]
    assert check["ok"] is False and obs["correct"] is False, check
    assert check["retraces_after_warmup"] == 0
    assert check["gap_sigma_max"] > TIGHT["gap_sigma_max"]
