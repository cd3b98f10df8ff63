"""The ``gdn_hybrid`` architecture's benchmark files: the counts pinned to a
hand count of the cut, the configuration and the traffic file, the readers'
helper on a hand-made trace, the four readers built on it, the planted
faults, and the new cell's rehearsal.  CPU only: counts and control flow,
no device metric."""

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
from benchmarks.layer_metrics import _linscopes as LS  # noqa: E402
from benchmarks.layer_metrics import _scopes as S  # noqa: E402
from benchmarks.layer_metrics import _subscopes as SS  # noqa: E402

CELL = "serve-hybrid-rollout"
CONFIG = ROOT / "benchmarks/configs/olmo-hybrid-7b-l12-serve.json"
TRAFFIC = ROOT / "benchmarks/workloads/rollout-backlog.json"
#: Olmo-Hybrid-7B's published config.json, the numbers
PUBLISHED = {
    "vocab_size": 100352, "hidden_size": 3840, "intermediate_size": 11008,
    "num_hidden_layers": 32, "num_attention_heads": 30,
    "num_key_value_heads": 30, "max_position_embeddings": 65536,
    "rms_norm_eps": 1e-06, "linear_num_key_heads": 30,
    "linear_num_value_heads": 30, "linear_key_head_dim": 96,
    "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4}
NEW_READERS = ("lin_step_ms_tput", "lin_step_roofline_tput",
               "lin_scan_ms_tput", "lin_scan_roofline_tput")


@pytest.fixture(scope="module")
def cfg_file():
    return json.loads(CONFIG.read_text())


@pytest.fixture(scope="module")
def counts():
    return harness.find_module("counts", "gdn_hybrid")


# ------------------------------------------------------------- the counts

def test_counts_are_the_cuts_arithmetic(cfg_file, counts):
    f = cfg_file["fields"]
    mlp = 3 * 3840 * 11008                                      # 126.8 M
    full = 4 * 3840 * 3840 + 2 * 3840                           # 59.0 M
    lin = 2 * 3840 * 2880 + 2 * 3840 * 5760 + 5760 * 3840 \
        + 2 * 3840 * 30 + 2 * 30 + 4 * 11520 + 192              # 88.7 M
    assert counts.full_layer_weight_count(f) == full == 58_990_080
    assert counts.linear_layer_weight_count(f) == lin == 88_750_332
    assert counts.conv_channels(f) == 11_520
    want = 3 * (mlp + full) + 9 * (mlp + lin) + 12 * 2 * 3840 \
        + 2 * 100_352 * 3840 + 3840
    assert counts.param_count(f) == want == 3_268_268_508
    # the issue's 3.266 G is without the small leaves (norms, the per-head
    # scalars' projections, the conv): they are 2.3 M
    assert abs(want - 3.266e9) < 3e6 and round(2 * want / 1e9, 2) == 6.54
    # a token caches K and V in the 3 full-attention layers only
    assert counts.kv_bytes_per_token(f) == 3 * 2 * 30 * 128 * 2 == 46_080
    tail = 3 * 11_520 * 2
    assert counts.slot_state_bytes(f) == 2_211_840 + tail
    assert 30 * 96 * 192 * 4 == 2_211_840
    assert counts.state_step_bytes(f, 64) == 9 * 64 * 2 * (2_211_840 + tail)
    assert counts.state_step_bytes(f, 64) / 1e9 == pytest.approx(2.63, abs=.01)
    live = 64 * 620.0
    assert counts.decode_step_bytes(f, live, live_slots=64) == pytest.approx(
        2 * (want - 100_352 * 3840) + live * 46_080
        + counts.state_step_bytes(f, 64))
    # the chunked scan at sub-chunks of 64, one head and sub-chunk by hand
    per = 64 * 64 * (6 * 96 + 4 * 192) + 64 ** 3 + 6 * 64 * 96 * 192
    assert per == 12_845_056
    assert counts.chunk_scan_flops(f, 320) == 9 * 30 * 5 * per
    assert counts.chunk_scan_flops(f, 96) == 9 * 30 * 1.5 * per
    assert counts.chunk_scan_bytes(f, 320) == 9 * 30 * (
        320 * ((2 * 96 + 2 * 192) * 2 + 8) + 2 * 96 * 192 * 4)
    # memory bound at the v5e's peaks: 88 us of FLOPs under 173 us of bytes
    assert counts.chunk_scan_flops(f, 320) / 197e12 \
        < counts.chunk_scan_bytes(f, 320) / 819e9


def test_counts_are_the_programs_own(cfg_file, counts):
    import math

    import jax
    from distributed_training_sandbox_tpu.models import gdn_hybrid as G
    from distributed_training_sandbox_tpu.models import transformer as T
    from distributed_training_sandbox_tpu.serving import kv_pool
    f = cfg_file["fields"]
    mcfg = harness.model_config(f)
    assert mcfg.param_count() == counts.param_count(f)
    shapes = jax.eval_shape(lambda k: T.init_params(k, mcfg),
                            jax.random.key(0))
    assert sum(math.prod(x.shape) for x in jax.tree.leaves(shapes)) \
        == counts.param_count(f)
    tiny = {**f, **cfg_file["rehearse"]["fields"]}
    assert harness.model_config(tiny).param_count() \
        == counts.param_count(tiny)
    assert G.slot_state_bytes(mcfg) == counts.slot_state_bytes(f)
    assert kv_pool.slot_state_bytes(mcfg) == 9 * counts.slot_state_bytes(f)
    assert kv_pool.paged_layers(mcfg) == 3
    assert G.SCAN_CHUNK == counts.SCAN_CHUNK == 64
    # what the pool holds a token: the counts' 46,080 B plus two zero heads
    assert kv_pool.paged_layers(mcfg) * kv_pool.token_row_bytes(mcfg) \
        == 49_152 == counts.kv_bytes_per_token(f) * 32 // 30


# ---------------------------------------------------------- the data files

def test_config_file_states_the_cut_and_keeps_every_published_width(
        cfg_file):
    f, fields = cfg_file, cfg_file["fields"]
    assert {k: v for k, v in f["published"].items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)} \
        == PUBLISHED
    assert f["reduced"] == ["num_hidden_layers"]
    for k, v in f["published"].items():
        # the top level of the file is the published config AS RUN
        assert f[k] == (12 if k == "num_hidden_layers" else v), k
        if k in fields:
            assert fields[k] == (12 if k == "num_hidden_layers" else v), k
    types = f["published"]["layer_types"]
    assert len(types) == 32 and types[:4] == ["linear_attention"] * 3 \
        + ["full_attention"] and types == types[:4] * 8
    assert fields["full_attention_interval"] == 4
    assert fields["num_hidden_layers"] % 4 == 0         # whole periods
    assert f["published"]["rope_parameters"] == {"rope_theta": None}
    assert "rope_theta" not in fields and fields["nope_interval"] == 0
    assert fields["linear_allow_neg_eigval"] is True
    assert f["architecture"] == "gdn_hybrid" and f["runner"] == "serve"
    assert f["deployment"]["chips_sharing_a_layer"] == 1
    assert f["state"]["dtype"] == "float32"
    assert f["state"]["bytes_per_slot_per_linear_layer"] == 2_211_840
    assert {"norm_placement", "qk_norm", "rotary", "weights"} \
        <= set(f["assumed"])
    assert "pipeline stages" in f["reduced_how"] and "13%" in f["reduced_how"]
    assert set(f["check"]) == {"gap_sigma_mean", "gap_sigma_max", "why"}
    # one argument off its default, with the spreads it was chosen on
    assert f["serve"]["engine"] == {"sync_every": 8}
    assert "1.3%" in f["serve"]["engine_why"]
    assert fields["dtype"] == "bfloat16"
    assert f["rehearse"]["fields"]["num_hidden_layers"] == 4


def test_traffic_file_carries_the_issues_parameters():
    t = json.loads(TRAFFIC.read_text())
    assert t["generator"] == "request_stream"
    assert t["params"] == {
        "arrival": {"process": "backlog", "count": 384},
        "prompt_len": {"dist": "lognormal", "median": 320, "sigma": 0.5,
                       "min": 128, "max": 768, "stratified": 8},
        "output_len": {"dist": "uniform", "min": 384, "max": 768,
                       "stratified": 8},
        "max_total": 1536}
    assert t["engine"] == {"max_batch": 64, "max_seq_len": 1536,
                           "page_size": 16, "prefill_chunk": 512}
    assert t["drain_s"] == 30.0 and t["trace"] == {"seconds": 8.0}
    assert t["check"]["requests"] == 2


def test_the_cell_reports_the_shared_readers_and_the_new_four():
    cell = harness.load_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "olmo-hybrid-7b-l12-serve", "rollout-backlog", 1)
    assert [m.name for m in cell.end_to_end] == ["serve_tokens_per_s"]
    assert {m.name for m in cell.per_layer} >= {
        "decode_ms_per_step_tput", "prefill_ms_per_chunk_tput",
        "decode_attn_ms_tput", "prefill_attn_ms_tput",
        "engine_batch_occupancy_tput", "sched_host_ms_per_round_tput",
        "serve_device_idle_pct_tput", "decode_inplace_share_tput",
        # the full-attention layers' prefill is the flash kernel, and the
        # linear layers' decode step the step kernel: both read 100 here
        "prefill_inplace_share_tput", "lin_step_inplace_share_tput",
        *NEW_READERS}
    counts = harness.cell_counts(cell)
    assert Path(counts.__file__).name == "gdn_hybrid.py"
    for other in ("serve-doc-batch", "serve-mla-moe-longgen"):
        assert not any(m.name.startswith("lin_")
                       for m in harness.load_cell(other).per_layer)


# ---------------------------------------------------------- the new names

def test_the_helpers_names_are_the_programs():
    from distributed_training_sandbox_tpu.utils import profiling
    assert LS.LINEAR_SUBSCOPES == profiling.LINEAR_SUBSCOPES
    assert not set(LS.LINEAR_SUBSCOPES) & (set(S.CATALOGUE)
                                           | set(SS.SUBSCOPES))


@pytest.mark.parametrize("path,want,above", [
    ("jit(<unknown>)/attn_core/lin_step/mul", "lin_step", "attn_core"),
    ("jit(<unknown>)/attn_core/lin_scan/while/body/dot_general", "lin_scan",
     "attn_core"),
    ("jit(<unknown>)/attn_qkv/lin_conv/concatenate", "lin_conv", "attn_qkv"),
    ("jit(<unknown>)/attn_qkv/dot_general", None, "attn_qkv"),
    ("jit(<unknown>)/attn_core/berlin_step_x/add", None, "attn_core"),
    ("", None, None), (None, None, None)])
def test_innermost_linear_subscope(path, want, above):
    assert LS.innermost(path) == want
    assert S.innermost(path) == above       # the catalogue's reader's name


def test_self_time_per_program_on_a_small_trace():
    us = 1e3
    decode, prefill = "jit__unknown(7)", "jit__unknown(9)"
    ops = [
        # decode launch 0..400: a fusion of 100 under lin_step that nests a
        # 30 op of lin_conv; the step's self time is 70
        ("fusion.1", 10 * us, 100 * us, "jit(<unknown>)/attn_core/lin_step/m"),
        ("copy.2", 20 * us, 30 * us, "jit(<unknown>)/attn_qkv/lin_conv/cat"),
        ("fusion.3", 200 * us, 50 * us, "jit(<unknown>)/attn_core/lin_step/r"),
        ("fusion.4", 300 * us, 40 * us, "jit(<unknown>)/attn_core/dot"),
        # prefill launch 500..900, cut by the window's end at 700
        ("fusion.5", 650 * us, 100 * us,
         "jit(<unknown>)/attn_core/lin_scan/while/body/dot"),
        ("while.6", 500 * us, 400 * us, "jit(<unknown>)/attn_core/lin_scan"),
    ]
    raw = S.ScopedRaw(devices={"/device:TPU:0": {
        "ops": ops, "modules": [(decode, 0.0, 400 * us),
                                (prefill, 500 * us, 400 * us)]}})
    got = LS.reduce(raw, (0.0, 700 * us))
    assert got == pytest.approx({
        (decode, "lin_step"): 120 * us, (decode, "lin_conv"): 30 * us,
        (prefill, "lin_scan"): 50 * us})        # the while is a container


# -------------------------------------------------------------- the readers

def _ctx(counts, fields, stats, **counters):
    return SimpleNamespace(
        trace=None, fields=fields, counts=counts,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        counters={"stats": stats, "engine": {"max_batch": 64}, **counters})


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_program_without_the_names_gives_the_new_readers_nothing(
        name, cfg_file, counts):
    """The parent's engine has neither the scopes nor the counters, and an
    untraced run has no table: each reader returns None, never raises."""
    mod = harness.find_module("layer_metrics", name)
    old = {"rounds": 9, "decode_steps": 36, "occupancy_sum": 50,
           "prefill_chunks": 4}
    assert mod.read(_ctx(counts, cfg_file["fields"], old)) is None
    assert (mod.LAYER, mod.MOVES, mod.RUNNERS) == (
        "kernels", "serve_tokens_per_s", ("serve",))
    assert mod.UNIT == ("%" if "roofline" in name else "ms")


def test_the_new_readers_arithmetic(monkeypatch, cfg_file, counts):
    f = cfg_file["fields"]
    stats = {"rounds": 10, "decode_steps": 40, "occupancy_sum": 640,
             "state_slot_steps": 40 * 60, "state_resets": 12,
             "prefill_chunks": 12, "lin_scan_rows": 12 * 320}
    ctx = _ctx(counts, f, stats)
    took = {(("lin_step",), "decode"): 6.0, (("lin_scan",), "prefill"): 4.0}
    monkeypatch.setattr(LS, "subscope_ms_per_launch",
                        lambda ctx, names, label: took.get((names, label)))
    step = harness.find_module("layer_metrics", "lin_step_ms_tput")
    scan = harness.find_module("layer_metrics", "lin_scan_ms_tput")
    assert step.read(ctx) == 6.0 and scan.read(ctx) == 4.0
    roof = harness.find_module("layer_metrics", "lin_step_roofline_tput")
    # 60 live slots x 9 layers x 2 x 2.28 MB at 819 GB/s = 3.01 ms of 6
    assert roof.read(ctx) == pytest.approx(
        100 * 9 * 60 * 2 * (2_211_840 + 69_120) / 819e9 / 6e-3)
    assert 45 < roof.read(ctx) < 55
    roof = harness.find_module("layer_metrics", "lin_scan_roofline_tput")
    least = max(counts.chunk_scan_flops(f, 320) / 197e12,
                counts.chunk_scan_bytes(f, 320) / 819e9)
    assert roof.read(ctx) == pytest.approx(100 * least / 4e-3)
    assert 3 < roof.read(ctx) < 6


# ----------------------------------------------------------- the rehearsals

def test_the_new_cells_rehearsal_passes(cell=CELL,
                                        reference="benchmarks/reference/"
                                                  "gdn_hybrid.py"):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks/run.py"), "--workload", cell,
         "--rehearse-cpu"], capture_output=True, text=True, timeout=600,
        cwd=str(ROOT), env=env)
    assert out.returncode == 0, out.stdout + out.stderr
    assert f"cell={cell}" in out.stdout
    assert "failed=0 reference_ok=True compiles_in_window=0" in out.stdout
    check = json.loads(out.stdout.split("rehearsal check: ", 1)[1])
    assert check["reference"] == reference
    assert check["retraces_after_warmup"] == 0
    assert check["tokens_checked"] > 0


# ------------------------------------------------- the check separates faults

def _drive(check, fault=None, engine=None, seed=11):
    """A rehearsal of the cell in this process (the harness's look for a
    chip skipped), held to ``check``; returns the runner's observation."""
    import contextlib
    import time
    cell = harness.load_cell(CELL)
    cell.config["check"].update(check)
    # sharper attention and closer logits than the cell's own scale: at the
    # rehearsal's 64-wide model a fault has few tokens to show in
    cell.config["serve"]["param_scale"] = 3.0
    cell.config["serve"]["engine"].update(engine or {})
    runner = harness.find_module("runners", cell.runner)
    ref = harness.find_module("reference", cell.architecture,
                              needs=runner.REFERENCE_EXPORTS)
    with fault() if fault else contextlib.nullcontext():
        obs = runner.run(cell, ref=ref, seed=seed, seconds=2.0, trace=False,
                         rehearse=True, watch=harness.CompileWatch(),
                         phases=harness.Phases(time.perf_counter()))
    assert obs["attempted"] > 0 and obs["failed"] == 0
    return obs


#: the rehearsal computes in float32, where the sound program's served
#: token is the reference's argmax (gap 0 at every position)
TIGHT = {"gap_sigma_mean": 0.002, "gap_sigma_max": 0.1}


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernels"])
def test_the_sound_program_is_correct_under_tight_limits(kernel):
    obs = _drive(TIGHT, engine={"paged_kernel": kernel})
    assert obs["correct"], obs["check"]
    s = obs["counters"]["stats"]
    assert s["state_slot_steps"] > 0 and s["lin_scan_rows"] > 0
    assert s["state_resets"] == s["admitted"] > 0
    assert (s["decode_inplace_steps"] > 0) == kernel
    assert (s["prefill_inplace_chunks"] > 0) == kernel


@pytest.mark.parametrize("fault", ["beta_without_its_factor_2",
                                   "padding_rows_update_state",
                                   "conv_tail_not_carried"])
def test_a_planted_fault_is_not_correct(fault):
    """A fault of ``gdn_hybrid_faults`` moves served tokens off the
    reference's argmax by more than the tight limits allow, with nothing
    else failing: no request is lost, nothing recompiles.  (``state_in_bf16``
    and int8 projections move no token of a 64-wide float32 model; the
    first is held on logits in ``tests/test_gdn_hybrid.py``, both on the
    chip: the configuration's ``check.why``.)"""
    from tests.benchmark import gdn_hybrid_faults
    obs = _drive(TIGHT, gdn_hybrid_faults.FAULTS[fault][0])
    check = obs["check"]
    assert check["ok"] is False and obs["correct"] is False, check
    assert check["retraces_after_warmup"] == 0
    assert check["gap_sigma_max"] > TIGHT["gap_sigma_max"]


@pytest.mark.parametrize("fault", ["state_in_bf16",
                                   "beta_without_its_factor_2",
                                   "conv_tail_not_carried",
                                   "padding_rows_update_state"])
def test_a_fault_changes_the_program_it_names_and_no_other(fault):
    """Lowered at the rehearsal's size: a decode fault changes the decode
    program's StableHLO and leaves the prefill program's as it was, a
    prefill fault the other way round."""
    import jax
    import jax.numpy as jnp
    from distributed_training_sandbox_tpu.models import transformer as T
    from distributed_training_sandbox_tpu.serving import engine as E
    from distributed_training_sandbox_tpu.serving.kv_pool import PagedKVPool
    from tests.benchmark import gdn_hybrid_faults
    cfg_file = json.loads(CONFIG.read_text())
    mcfg = harness.model_config({**cfg_file["fields"],
                                 **cfg_file["rehearse"]["fields"]})
    B, P, page, chunk = 4, 8, 8, 16
    sd = jax.ShapeDtypeStruct
    params = jax.eval_shape(lambda: T.init_params(jax.random.key(0), mcfg))
    bufs = jax.eval_shape(
        lambda: PagedKVPool(mcfg, B * P + 1, page, n_slots=B).bufs)
    i32 = lambda *shape: sd(shape, jnp.int32)  # noqa: E731

    def texts():
        dec = E.make_serve_decode_step(mcfg).trace(
            bufs, params, i32(B, P), i32(B), i32(B), i32(B),
            sd((B,), jnp.bool_), i32(1)).lower().as_text()
        pre = E.make_serve_prefill_step(mcfg).trace(
            bufs, params, i32(1, P), i32(1, chunk), i32(), i32(),
            i32()).lower().as_text()
        return {"decode": dec, "prefill": pre}

    sound = texts()
    plant, names = gdn_hybrid_faults.FAULTS[fault]
    with plant():
        faulty = texts()
    other = {"decode": "prefill", "prefill": "decode"}[names]
    assert faulty[names] != sound[names]
    assert faulty[other] == sound[other]
