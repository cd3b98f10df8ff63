"""The ``ssm_moe`` architecture's benchmark files: the configuration
against the catalog's row key by key, the counts pinned to a hand count of
the cut, the cell's fifteen first readers, the two readers of its conv
and state (entries of ``BENCHMARK.json`` since PR 46), the planted faults,
and the new cell's rehearsal.  CPU only: counts and control flow, no device
metric."""

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
from benchmarks.layer_metrics import _subscopes as SS  # noqa: E402

CELL = "serve-ssm-moe-sessions"
CONFIG = ROOT / "benchmarks/configs/granite-4.0-h-small-ep4-l10-serve.json"
TRAFFIC = ROOT / "benchmarks/workloads/session-backlog.json"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
#: granite-4.0-h-small's published config.json, the numbers (the catalog's
#: row 24, copied: the test below holds the copy to the row where the
#: catalog is installed)
PUBLISHED = {
    "attention_multiplier": 0.0078125, "embedding_multiplier": 12,
    "hidden_size": 4096, "intermediate_size": 768, "logits_scaling": 16,
    "mamba_chunk_size": 256, "mamba_d_conv": 4, "mamba_d_head": 64,
    "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
    "mamba_n_heads": 128, "max_position_embeddings": 131072,
    "num_attention_heads": 32, "num_experts_per_tok": 10,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 72, "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-05, "rope_theta": 10000,
    "shared_intermediate_size": 1536, "vocab_size": 100352}
AS_RUN = {"num_hidden_layers": 10, "num_local_experts": 18}
SHARED = ("decode_ms_per_step_tput", "prefill_ms_per_chunk_tput",
          "decode_attn_ms_tput", "prefill_attn_ms_tput",
          "engine_batch_occupancy_tput", "sched_host_ms_per_round_tput",
          "serve_device_idle_pct_tput", "decode_inplace_share_tput")
MOE = ("moe_experts_ms_tput", "moe_experts_roofline_tput",
       "moe_tokens_per_expert_tput")
LIN = ("lin_step_ms_tput", "lin_step_roofline_tput", "lin_scan_ms_tput",
       "lin_scan_roofline_tput")
#: entries since PR 46 (files with tests and no entry until then)
NEW_READERS = ("lin_conv_ms_tput", "lin_state_bytes_share_tput")


def _numbers(d):
    return {k: v for k, v in d.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


@pytest.fixture(scope="module")
def cfg_file():
    return json.loads(CONFIG.read_text())


@pytest.fixture(scope="module")
def counts():
    return harness.find_module("counts", "ssm_moe")


# ---------------------------------------------------------- the data files

def test_config_file_is_the_catalog_row_key_by_key(cfg_file):
    f, fields = cfg_file, cfg_file["fields"]
    assert _numbers(f["published"]) == PUBLISHED
    if CATALOG.is_file():
        row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
                   if r["name"] == "granite-4.0-h-small")
        assert f["published"] == row["config"]
        assert f["source"] == row["source_url"]
    assert f["reduced"] == ["num_hidden_layers", "num_local_experts"]
    for k, v in f["published"].items():
        # the top level of the file is the published config AS RUN; a list
        # (layer_types) is copied whole
        assert f[k] == AS_RUN.get(k, v), k
        if k in fields:
            assert fields[k] == AS_RUN.get(k, v), k
    kinds = f["published"]["layer_types"]
    assert len(kinds) == 40 and [i for i, k in enumerate(kinds)
                                 if k == "attention"] == [5, 15, 25, 35]
    # the list whole in fields too: the program runs its first ten, one
    # whole period
    assert fields["layer_types"] == kinds and fields["num_hidden_layers"] == 10
    assert kinds[10:20] == kinds[:10] == kinds[20:30] == kinds[30:]
    assert fields["router_width"] == 72 and fields["expert_offset"] == 0
    assert fields["tie_word_embeddings"] is True
    assert f["published"]["position_embedding_type"] == "nope"
    assert f["published"]["mamba_conv_bias"] is True
    assert f["architecture"] == "ssm_moe" and f["runner"] == "serve"
    assert len(f["source"]) <= 200 and len(f["why"]) <= 200
    d = f["deployment"]
    assert (d["chips"], d["chips_sharing_a_layer"]) == (1, 4)
    assert d["experts_held_here"] == [0, 17] and d["layers_held_here"] == 10
    assert f["state"]["dtype"] == "float32"
    assert f["state"]["bytes_per_slot_per_mamba_layer"] == 4_194_304
    assert {"ssm_parameters", "dt_clamp", "gated_norm", "state_dtype",
            "in_projection", "router", "attention", "weights"} \
        == set(f["assumed"])
    assert "doubles the state's bytes" in f["assumed"]["state_dtype"]
    assert "0.02 / embedding_multiplier" in f["assumed"]["weights"]
    assert set(f["check"]) == {"gap_sigma_mean", "gap_sigma_max", "why"}
    assert set(f["serve"]["engine"]) == {"prefill_chunks_per_round",
                                         "sync_every"}
    assert fields["dtype"] == "bfloat16"
    r = f["rehearse"]["fields"]
    assert (r["num_local_experts"], r["router_width"],
            r["num_experts_per_tok"]) == (8, 12, 3)
    assert "num_hidden_layers" not in r          # the whole period of ten
    entry = next(c for c in harness.load_benchmark()["configs"]
                 if c["name"] == "granite-4.0-h-small-ep4-l10-serve")
    assert entry["reduced"] == f["reduced"] and entry["source"] == f["source"]
    assert entry["why"] == f["why"]


def test_the_traffic_file_is_the_issues():
    t = json.loads(TRAFFIC.read_text())
    p = t["params"]
    assert p["arrival"] == {"process": "backlog", "count": 512}
    assert p["prompt_len"] == {"dist": "lognormal", "median": 1536,
                               "sigma": 0.8, "min": 256, "max": 4096,
                               "stratified": 8}
    assert p["output_len"] == {"dist": "uniform", "min": 256, "max": 768,
                               "stratified": 8}
    assert p["max_total"] == 4864 == 4096 + 768
    assert t["engine"] == {"max_batch": 96, "max_seq_len": 5120,
                           "page_size": 16, "prefill_chunk": 512}
    assert t["drain_s"] == 45.0 and t["trace"] == {"seconds": 8.0}
    # the check's block divides the padded length: no fall-back to all rows
    assert p["max_total"] % t["check"]["block"] == 0
    assert t["engine"]["max_seq_len"] % t["engine"]["prefill_chunk"] == 0
    assert t["generator"] == "request_stream" and "who" in t
    cells = [w["name"] for w in harness.load_benchmark()["workloads"]
             if w["traffic"] == "session-backlog"]
    assert cells[0] == CELL


def test_the_cell_reports_the_fifteen_first_readers():
    cell = harness.load_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "granite-4.0-h-small-ep4-l10-serve", "session-backlog", 1)
    assert len(cell.why) <= 200 and "4x under" in cell.why
    assert "not engine defaults" in cell.why
    assert [m.name for m in cell.end_to_end] == ["serve_tokens_per_s"]
    assert {m.name for m in cell.per_layer} >= {*SHARED, *MOE, *LIN}
    assert Path(harness.cell_counts(cell).__file__).name == "ssm_moe.py"
    for entry in harness.load_benchmark()["per_layer"]:
        if CELL in entry.get("workloads", ()):
            assert entry["moves"] == "serve_tokens_per_s"


def test_the_cell_reports_the_two_new_readers():
    """The two are entries of ``BENCHMARK.json`` (PR 46; until then files
    beside a pinned list): each says what its module says, the cell lists
    them and its counts module has what they call; a cell with no linear
    layer reports no ``lin_`` reader."""
    entries = {m["name"]: m for m in harness.load_benchmark()["per_layer"]}
    for name in NEW_READERS:
        mod, e = harness.find_module("layer_metrics", name), entries[name]
        assert {k: e[k] for k in e if k != "workloads"} == {
            "name": name, "unit": mod.UNIT, "layer": mod.LAYER,
            "better": "lower" if name.endswith("_ms_tput") else "higher",
            "source": "device_trace" if name.endswith("_ms_tput")
            else "program_counter", "moves": mod.MOVES}
        assert e["workloads"][0] == CELL
    cell = harness.load_cell(CELL)
    assert {m.name for m in cell.per_layer} >= {*SHARED, *MOE, *LIN,
                                                *NEW_READERS}
    counts = harness.cell_counts(cell)
    assert Path(counts.__file__).name == "ssm_moe.py"
    for name in NEW_READERS:
        for need in getattr(harness.find_module("layer_metrics", name),
                            "COUNTS", ()):
            assert hasattr(counts, need), need
    for other in ("serve-doc-batch", "serve-mla-moe-longgen",
                  "serve-swa-moe-mixedlen"):
        assert not any(m.name.startswith("lin_")
                       for m in harness.load_cell(other).per_layer)


# ------------------------------------------------------------- the counts

def test_counts_are_the_cuts_arithmetic(cfg_file, counts):
    f = cfg_file["fields"]
    mamba = 4096 * (8192 + 8448 + 128) + 8192 * 4096 + 5 * 8448 \
        + 3 * 128 + 8192                                       # 102.3 M
    attn = 4096 * 128 * (2 * 32 + 2 * 8)                       # 41.9 M
    rest = 4096 * 72 + 3 * 4096 * 1536 + 2 * 4096              # 19.2 M
    expert = 3 * 4096 * 768
    assert counts.mamba_layer_weight_count(f) == mamba == 102_286_976
    assert counts.attention_layer_weight_count(f) == attn == 41_943_040
    assert counts.moe_rest_weight_count(f) == rest == 19_177_472
    assert counts.expert_weight_count(f) == expert == 9_437_184
    assert counts.conv_channels(f) == 8448 == 8192 + 2 * 128
    want = 9 * (mamba + rest + 18 * expert) + (attn + rest + 18 * expert) \
        + 100_352 * 4096 + 4096                    # tied: one matrix
    assert counts.param_count(f) == want == 3_264_039_552
    assert round(want / 1e9, 2) == 3.26 and round(2 * want / 1e9, 2) == 6.53
    # two chips a layer would hold 36 experts here: 9.93 GB
    assert round(2 * (want + 10 * 18 * expert) / 1e9, 2) == 9.93
    assert counts.kv_bytes_per_token(f) == 2 * 8 * 128 * 2 == 4096
    assert counts.state_bytes(f) == 128 * 64 * 128 * 4 == 4_194_304
    assert counts.slot_state_bytes(f) == 4_194_304 + 3 * 8448 * 2 == 4_244_992
    assert counts.state_step_bytes(f, 96) == 96 * 9 * 2 * 4_244_992 \
        == 7_335_346_176
    assert counts.expert_step_bytes(f, 10 * 18) == 180 * expert * 2
    live = 96 * 2300.0
    assert counts.decode_step_bytes(f, live, live_slots=96) == pytest.approx(
        2 * want + live * 4096 + 7_335_346_176)
    assert counts.decode_step_bytes(f, live, live_slots=96) / 1e9 \
        == pytest.approx(14.8, abs=.1)
    # 12 of 18 experts a layer touched: the idle ones' weights are not read
    assert counts.decode_step_bytes(f, live, live_slots=96,
                                    experts_touched=10 * 12) \
        == pytest.approx(counts.decode_step_bytes(f, live, live_slots=96)
                         - 10 * 6 * expert * 2)
    # the SSD scan at blocks of 256: C B^T once a block for all heads, a
    # head's decay-weighted product and its two products with the state
    per_block = 2 * 256 * 256 * 128 \
        + 128 * (2 * 256 * 256 * 64 + 4 * 256 * 128 * 64)
    assert counts.chunk_scan_flops(f, 512) == 9 * 2 * per_block \
        == 38_956_695_552
    assert counts.chunk_scan_flops(f, 384) == 9 * 1.5 * per_block
    assert counts.chunk_scan_bytes(f, 512) == 9 * (
        512 * ((2 * 8192 + 2 * 128) * 2 + 4 * 128) + 2 * 4_194_304)
    # compute bound by a hair: 0.198 ms of products, 0.28 of bytes
    assert counts.chunk_scan_flops(f, 512) / 197e12 == pytest.approx(
        1.98e-4, rel=.01)
    assert counts.SCAN_BLOCK == 256 == f["mamba_chunk_size"]


def test_counts_are_the_programs_own(cfg_file, counts):
    import math

    import jax
    from distributed_training_sandbox_tpu.models import ssm_moe as M
    from distributed_training_sandbox_tpu.models import transformer as T
    from distributed_training_sandbox_tpu.serving import kv_pool
    f = cfg_file["fields"]
    mcfg = harness.model_config(f)
    assert mcfg.ssm_moe and mcfg.param_count() == counts.param_count(f)
    shapes = jax.eval_shape(lambda k: T.init_params(k, mcfg),
                            jax.random.key(0))
    assert sum(math.prod(x.shape) for x in jax.tree.leaves(shapes)) \
        == counts.param_count(f)
    tiny = {**f, **cfg_file["rehearse"]["fields"]}
    assert harness.model_config(tiny).param_count() \
        == counts.param_count(tiny)
    assert M.slot_state_bytes(mcfg) == counts.slot_state_bytes(f)
    assert kv_pool.slot_state_bytes(mcfg) == 9 * counts.slot_state_bytes(f)
    assert kv_pool.paged_layers(mcfg) == 1 and not kv_pool.slab_pool(mcfg)
    assert M.MAMBA_CHUNK_SIZE == counts.SCAN_BLOCK
    assert kv_pool.paged_layers(mcfg) * kv_pool.token_row_bytes(mcfg) \
        == 4096 == counts.kv_bytes_per_token(f)
    # the memory the cell fills: weights + state slots + pages, of 16.9 GB
    t = json.loads(TRAFFIC.read_text())["engine"]
    pages = t["max_batch"] * t["max_seq_len"] // t["page_size"] + 1
    held = 2 * counts.param_count(f) \
        + t["max_batch"] * kv_pool.slot_state_bytes(mcfg) \
        + pages * t["page_size"] * 4096
    assert round(held / 1e9, 1) == 12.2


# ------------------------------------------------------------- the scopes

def test_the_engine_opens_the_catalogues_scopes_for_this_block():
    """Lowered at the rehearsal's size with debug info: the block's
    programs carry the second level the other hybrids open, under the same
    names, and no name of its own."""
    import jax
    import jax.numpy as jnp
    from distributed_training_sandbox_tpu.models import transformer as T
    from distributed_training_sandbox_tpu.serving import engine as E
    from distributed_training_sandbox_tpu.serving.kv_pool import PagedKVPool
    from distributed_training_sandbox_tpu.utils import profiling
    B, P, page, chunk = 4, 8, 8, 16
    sd = jax.ShapeDtypeStruct
    i32 = lambda *shape: sd(shape, jnp.int32)  # noqa: E731
    f = json.loads(CONFIG.read_text())
    mcfg = harness.model_config({**f["fields"], **f["rehearse"]["fields"]})
    params = jax.eval_shape(lambda: T.init_params(jax.random.key(0), mcfg))
    bufs = jax.eval_shape(
        lambda: PagedKVPool(mcfg, B * P + 1, page, n_slots=B).bufs)
    assert E.device_counters(mcfg) == (
        "moe_assignments", "moe_assignments_held", "moe_experts_touched",
        "moe_expert_layer_steps", "state_slot_steps")
    dec = E.make_serve_decode_step(mcfg).trace(
        bufs, params, i32(B, P), i32(B), i32(B), i32(B), sd((B,), jnp.bool_),
        i32(5)).lower().as_text(debug_info=True)
    pre = E.make_serve_prefill_step(mcfg).trace(
        bufs, params, i32(1, P), i32(1, chunk), i32(), i32(),
        i32()).lower().as_text(debug_info=True)
    for name in ("moe_route", "moe_experts", "moe_shared", "lin_conv"):
        assert f"/{name}/" in dec and f"/{name}/" in pre, name
    assert "attn_core/lin_step/" in dec and "/lin_scan/" not in dec
    assert "attn_core/lin_scan/" in pre and "/lin_step/" not in pre
    assert "attn_core/attn_paged/" in dec and "attn_core/attn_paged/" in pre
    assert "attn_qkv/lin_conv/" in dec
    assert set(LS.LINEAR_SUBSCOPES) == set(profiling.LINEAR_SUBSCOPES)


# -------------------------------------------------------------- the readers

def _ctx(counts, fields, stats, **counters):
    return SimpleNamespace(
        trace=None, fields=fields, counts=counts,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        counters={"stats": stats, "engine": {"max_batch": 96}, **counters})


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_program_without_the_names_gives_the_new_readers_nothing(
        name, cfg_file, counts):
    """The parent's engine has not this block, and an untraced run has no
    table: each reader returns None, never raises."""
    mod = harness.find_module("layer_metrics", name)
    old = {"rounds": 9, "decode_steps": 36, "occupancy_sum": 50,
           "prefill_chunks": 4}
    assert mod.read(_ctx(counts, cfg_file["fields"], old)) is None
    assert mod.read(_ctx(counts, cfg_file["fields"], old, kv_valid_sum=9000,
                         kv_samples=9)) is None
    assert (mod.MOVES, mod.RUNNERS, mod.LAYER) == (
        "serve_tokens_per_s", ("serve",), "model step")
    assert mod.UNIT == ("ms" if name.endswith("_ms_tput") else "%")


def test_the_readers_arithmetic(monkeypatch, cfg_file, counts):
    f = cfg_file["fields"]
    stats = {"rounds": 10, "decode_steps": 80, "occupancy_sum": 800,
             "moe_experts_touched": 80 * 10 * 15,
             "moe_assignments_held": 80 * 10 * 15 * 12,
             "state_slot_steps": 80 * 90, "prefill_chunks": 60,
             "lin_scan_rows": 60 * 448}
    ctx = _ctx(counts, f, stats, kv_valid_sum=10 * 90 * 2000, kv_samples=10)
    monkeypatch.setattr(
        LS, "subscope_ms_per_launch",
        lambda ctx, names, label: {(("lin_conv",), "decode"): 0.8,
                                   (("lin_step",), "decode"): 11.0,
                                   (("lin_scan",), "prefill"): 1.3}.get(
            (names, label)))
    monkeypatch.setattr(
        SS, "subscope_ms_per_launch",
        lambda ctx, names, label: {(("moe_experts",), "decode"): 4.0}.get(
            (names, label)))
    read = lambda name: harness.find_module(  # noqa: E731
        "layer_metrics", name).read(ctx)
    assert read("lin_conv_ms_tput") == 0.8
    # 90 live slots: state and tails 6.88 GB of a step's 13.5 GB
    state = 9 * 90 * 2 * 4_244_992
    whole = 2 * (3_264_039_552 - 10 * 3 * 9_437_184) + 90 * 2000 * 4096 \
        + state
    assert read("lin_state_bytes_share_tput") == pytest.approx(
        100 * state / whole)
    assert 50 < read("lin_state_bytes_share_tput") < 52
    # the accepted readers the cell joins count with THIS block's counts
    assert read("lin_step_roofline_tput") == pytest.approx(
        100 * state / 819e9 / 11e-3)
    assert 75 < read("lin_step_roofline_tput") < 78
    assert read("moe_experts_roofline_tput") == pytest.approx(
        100 * 150 * 9_437_184 * 2 / 819e9 / 4e-3)
    assert read("moe_tokens_per_expert_tput") == 12
    # 448 valid rows a chunk: 1.75 blocks; the bytes bound it
    least = max(9 * 1.75 * 2_164_260_864 / 197e12,
                counts.chunk_scan_bytes(f, 448) / 819e9)
    assert read("lin_scan_roofline_tput") == pytest.approx(
        100 * least / 1.3e-3)
    assert 15 < read("lin_scan_roofline_tput") < 25


# ----------------------------------------------------------- the rehearsals

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
    assert check["reference"] == "benchmarks/reference/ssm_moe.py"
    assert check["retraces_after_warmup"] == 0
    assert check["tokens_checked"] > 0


# ------------------------------------------------- the check separates faults

def _drive(check, fault=None, engine=None, seed=11, fields=None):
    """A rehearsal of the cell in this process (the harness's look for a
    chip skipped), held to ``check``; returns the runner's observation."""
    import contextlib
    import time
    cell = harness.load_cell(CELL)
    cell.config["check"].update(check)
    # closer logits than the cell's own scale: at the rehearsal's 64-wide
    # model a fault has few tokens to show in
    cell.config["serve"]["param_scale"] = 4.0
    cell.config["serve"]["engine"].update(engine or {})
    cell.config["fields"].update(fields or {})
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
    assert s["moe_expert_layer_steps"] == 10 * s["decode_steps"]
    assert 0 < s["moe_experts_touched"] <= s["moe_assignments_held"] \
        <= s["moe_assignments"] == 3 * 10 * s["state_slot_steps"]
    assert (s["decode_inplace_steps"] > 0) == kernel
    assert (s["lin_step_inplace_steps"] == s["decode_steps"]) == kernel
    assert (s["prefill_inplace_chunks"] > 0) == kernel


#: the faults that move a served token of the 64-wide float32 rehearsal
MOVES_TOKENS = ("dt_without_softplus", "conv_bias_left_out",
                "dskip_left_out", "residual_multiplier_left_out")


@pytest.mark.parametrize("fault", MOVES_TOKENS)
def test_a_planted_fault_is_not_correct(fault):
    """A fault of ``ssm_moe_faults`` moves served tokens off the
    reference's argmax by more than the tight limits allow, with nothing
    else failing: no request is lost, nothing recompiles.  (A bfloat16
    state, int8 projections, the decay applied after the update, the
    attention's scale and a rotary embedding move no token of a 64-wide
    float32 model with one attention layer in ten; they are held on logits
    or on the recurrence's outputs in ``tests/test_ssm_moe.py`` and on the
    chip: the configuration's ``check.why``.  ``logits_scaling`` cannot
    move a greedy token at all.)"""
    from tests.benchmark import ssm_moe_faults
    obs = _drive(TIGHT, ssm_moe_faults.FAULTS[fault][0])
    check = obs["check"]
    assert check["ok"] is False and obs["correct"] is False, check
    assert check["retraces_after_warmup"] == 0
    assert check["gap_sigma_mean"] > TIGHT["gap_sigma_mean"]


def test_logits_scaling_cannot_move_a_greedy_token():
    from tests.benchmark import ssm_moe_faults
    obs = _drive(TIGHT, ssm_moe_faults.FAULTS["logits_scaling_left_out"][0])
    assert obs["correct"] and obs["check"]["gap_sigma_max"] == 0.0


def test_the_int8_control_lowers_the_program_and_not_the_cells_fields():
    from tests.benchmark import ssm_moe_faults
    fields = json.loads(CONFIG.read_text())["fields"]
    plant, names = ssm_moe_faults.FAULTS["matmuls_in_int8"]
    assert names == "both"
    with plant():
        assert harness.model_config(fields).matmul_precision == "int8"
    assert harness.model_config(fields).matmul_precision == "bf16"


@pytest.mark.parametrize("fault,programs", [
    (name, programs) for name, (_, programs) in sorted(
        __import__("tests.benchmark.ssm_moe_faults",
                   fromlist=["FAULTS"]).FAULTS.items())
    if name != "matmuls_in_int8"])
def test_a_fault_changes_the_programs_it_says(fault, programs):
    """Lowered at the rehearsal's size: a decode-step fault leaves the
    prefill program's StableHLO as it was; the attention's scale and the
    logits' scaling change both."""
    import jax
    import jax.numpy as jnp
    from distributed_training_sandbox_tpu.models import transformer as T
    from distributed_training_sandbox_tpu.serving import engine as E
    from distributed_training_sandbox_tpu.serving.kv_pool import PagedKVPool
    from tests.benchmark import ssm_moe_faults
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
            sd((B,), jnp.bool_), i32(5)).lower().as_text()
        pre = E.make_serve_prefill_step(mcfg).trace(
            bufs, params, i32(1, P), i32(1, chunk), i32(), i32(),
            i32()).lower().as_text()
        return {"decode": dec, "prefill": pre}

    sound = texts()
    with ssm_moe_faults.FAULTS[fault][0]():
        faulty = texts()
    assert faulty["decode"] != sound["decode"]
    assert (faulty["prefill"] != sound["prefill"]) == (programs == "both")
