"""The ``cca_moe`` architecture's benchmark files: the configuration against
the catalog's row key by key, the counts pinned to a hand count of the cut,
the readers the cell joins and its three new ones, the planted faults, and
the new cell's rehearsal.  CPU only: counts and control flow, no device
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
from benchmarks.layer_metrics import _attnscopes as AS  # noqa: E402
from benchmarks.layer_metrics import _ccascopes as CS  # noqa: E402
from benchmarks.layer_metrics import _subscopes as SS  # noqa: E402

CELL = "serve-cca-moe-longgen"
NAME = "zaya1-8b-l16-serve"
CONFIG = ROOT / f"benchmarks/configs/{NAME}.json"
TRAFFIC = ROOT / "benchmarks/workloads/reasoning-backlog.json"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
#: ZAYA1-8B's published config.json, the numbers (the catalog's row, copied:
#: the test below holds the copy to the row where the catalog is installed)
PUBLISHED = {
    "cca_time0": 2, "cca_time1": 2, "head_dim": 128, "hidden_size": 2048,
    "max_position_embeddings": 131072, "moe_intermediate_size": 2048,
    "num_attention_heads": 8, "num_experts": 16, "num_experts_per_tok": 1,
    "num_hidden_layers": 40, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-05,
    "router_hidden_size": 256, "vocab_size": 262272}
AS_RUN = {"num_hidden_layers": 16}
SHARED = ("sched_host_ms_per_round_tput", "engine_batch_occupancy_tput",
          "decode_ms_per_step_tput", "prefill_ms_per_chunk_tput",
          "serve_device_idle_pct_tput", "decode_attn_ms_tput",
          "prefill_attn_ms_tput", "decode_inplace_share_tput")
CROSSINGS = ("round_idle_wake_ms_tput", "round_idle_read_ms_tput",
             "round_idle_hostwork_ms_tput",
             "round_idle_launch_latency_ms_tput", "d2h_reads_per_round_tput",
             "h2d_puts_per_round_tput")
MOE = ("moe_experts_ms_tput", "moe_experts_roofline_tput",
       "moe_tokens_per_expert_tput")
REST = ("prefill_head_share_tput", "prefill_inplace_share_tput",
        "moe_route_ms_tput", "paged_attn_ms_tput",
        "paged_attn_roofline_tput")
NEW_READERS = ("cca_conv_ms_tput", "cca_conv_roofline_tput",
               "moe_held_share_tput")
LAYER_EXPERTS = 16 * 3 * 2048 * 2048
LAYER = 2048 * 1536 + 1024 * 2048 + 4 * 1280 + 2 * 10 * 128 * 128 + 2 \
    + 659_984 + LAYER_EXPERTS + 2 * 2048


def _numbers(d):
    return {k: v for k, v in d.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


@pytest.fixture(scope="module")
def cfg_file():
    return json.loads(CONFIG.read_text())


@pytest.fixture(scope="module")
def counts():
    return harness.find_module("counts", "cca_moe")


# ---------------------------------------------------------- the data files

def test_config_file_is_the_catalog_row_key_by_key(cfg_file):
    f, fields = cfg_file, cfg_file["fields"]
    assert _numbers(f["published"]) == PUBLISHED
    if CATALOG.is_file():
        row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
                   if r["name"] == "ZAYA1-8B")
        # the published config has no dense MLP width: the file carries the
        # key as null, for the layout test's width list
        assert f["published"] == {**row["config"], "intermediate_size": None}
        assert "intermediate_size" not in row["config"]
        assert f["source"] == row["source_url"]
    assert f["reduced"] == ["num_hidden_layers"]
    for k, v in f["published"].items():
        # the top level of the file is the published config AS RUN; a list
        # or a group (layer_types, rope_parameters) is copied whole
        assert f[k] == AS_RUN.get(k, v), k
        if k in fields:
            assert fields[k] == AS_RUN.get(k, v), k
    assert f["published"]["layer_types"] == ["hybrid"] * 40
    assert fields["intermediate_size"] is None
    assert fields["rope_theta"] \
        == f["published"]["rope_parameters"]["hybrid"]["rope_theta"] == 5e6
    assert (fields["router_width"], fields["num_experts"],
            fields["expert_offset"]) == (16, 16, 0)        # every expert held
    assert fields["norm_topk_prob"] is False
    assert fields["tie_word_embeddings"] is True
    assert f["published"]["sliding_window"] is None
    assert f["architecture"] == "cca_moe" and f["runner"] == "serve"
    assert len(f["source"]) <= 200 and len(f["why"]) <= 200
    d = f["deployment"]
    assert (d["chips"], d["chips_sharing_a_layer"]) == (1, 1)
    assert d["experts_held_here"] == [0, 15] and d["layers_held_here"] == 16
    assert d["pipeline_stages"] == [16, 16, 8]
    assert {"conv_stages", "conv_grouping_biases_padding", "qk_mean",
            "value_shift", "norm_temperature_rotary", "attention_scale",
            "router_mlp", "init", "projections"} == set(f["assumed"])
    assert {"router_state_across_layers", "residual_scaling",
            "router_balancing_biases", "skip_choice"} == set(f["not_run"])
    assert "intermediate_size" in f["reduced_how"]
    assert "11%" in f["reduced_how"] and "16 | 16 | 8" in f["reduced_how"]
    assert set(f["check"]) == {"gap_sigma_mean", "gap_sigma_max", "why"}
    assert set(f["serve"]) >= {"param_scale", "engine", "why"}
    assert fields["dtype"] == "bfloat16"
    r = f["rehearse"]["fields"]
    assert (r["num_experts"], r["router_width"]) == (8, 8)
    entry = next(c for c in harness.load_benchmark()["configs"]
                 if c["name"] == NAME)
    assert entry["reduced"] == f["reduced"] and entry["source"] == f["source"]
    assert entry["why"] == f["why"]
    assert entry["file"] == f"benchmarks/configs/{NAME}.json"


def test_the_traffic_file_is_cells_6_and_8s_unedited():
    t = json.loads(TRAFFIC.read_text())
    p = t["params"]
    assert p["arrival"] == {"process": "backlog", "count": 320}
    assert p["prompt_len"] == {"dist": "lognormal", "median": 768,
                               "sigma": 0.5, "min": 256, "max": 2048,
                               "stratified": 8}
    assert p["output_len"] == {"dist": "uniform", "min": 384, "max": 768,
                               "stratified": 8}
    assert p["max_total"] == 2816
    assert t["engine"] == {"max_batch": 64, "max_seq_len": 4096,
                           "page_size": 16, "prefill_chunk": 256}
    assert p["max_total"] % t["check"]["block"] == 0
    cells = [w["name"] for w in harness.load_benchmark()["workloads"]
             if w["traffic"] == "reasoning-backlog"]
    assert cells == ["serve-mla-moe-longgen", "serve-hybrid-moe-longgen",
                     CELL]


def test_the_cell_reports_the_readers_it_joins_and_its_own():
    bm = harness.load_benchmark()
    cell = harness.load_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        NAME, "reasoning-backlog", 1)
    assert len(cell.why) <= 200 and "EVERY expert choice held" in cell.why
    assert [m.name for m in cell.end_to_end] == ["serve_tokens_per_s"]
    names = {m.name for m in cell.per_layer}
    assert names >= {*SHARED, *CROSSINGS, *MOE, *REST, *NEW_READERS}
    assert len({*SHARED, *CROSSINGS, *MOE, *REST}) == 22
    assert not any(n.startswith(("lin_", "swa_", "mla_", "kv_window"))
                   for n in names)
    assert Path(harness.cell_counts(cell).__file__).name == "cca_moe.py"
    entries = {m["name"]: m for m in bm["per_layer"]}
    for entry in bm["per_layer"]:
        if CELL in entry.get("workloads", ()):
            assert entry["moves"] == "serve_tokens_per_s"
    for name in NEW_READERS:
        mod, e = harness.find_module("layer_metrics", name), entries[name]
        assert e == {
            "name": name, "unit": mod.UNIT, "layer": mod.LAYER,
            "better": "lower" if name.endswith("_ms_tput") else "higher",
            "source": "program_counter" if name.startswith("moe_")
            else "device_trace", "moves": mod.MOVES, "workloads": [CELL]}
        for need in getattr(mod, "COUNTS", ()):
            assert hasattr(harness.cell_counts(cell), need), need
    # twelve cells, one on four chips
    assert len(bm["workloads"]) >= 12
    assert sum(w["chips"] == 4 for w in bm["workloads"]) == 1
    assert bm["workloads"][11]["name"] == CELL
    assert bm["configs"][8]["name"] == NAME


# ------------------------------------------------------------- the counts

def test_counts_are_the_cuts_arithmetic(cfg_file, counts):
    f = cfg_file["fields"]
    assert counts.conv_weight_count(f) == 4 * 1280 + 327_680 == 332_800
    assert counts.attention_weight_count(f) == 5_575_682
    assert counts.router_weight_count(f) == 659_984
    assert counts.expert_weight_count(f) == 3 * 2048 * 2048 == 12_582_912
    assert counts.layer_weight_count(f) == LAYER == 207_566_354
    want = 16 * LAYER + 262_272 * 2048 + 2048          # tied: one matrix
    assert counts.param_count(f) == want == 3_858_196_768
    assert round(2 * want / 1e9, 2) == 7.72
    # the whole model: 40 layers, 17.7 GB, on no one chip
    assert round(2 * (want + 24 * LAYER) / 1e9, 1) == 17.7
    assert counts.kv_bytes_per_token(f) == 16 * 1024
    assert counts.tail_bytes(f) == (2 * 1280 + 128) * 2 == 5376
    assert counts.expert_step_bytes(f, 1) == 25_165_824
    assert counts.expert_step_bytes(f, 16 * 15.7) == pytest.approx(
        16 * 15.7 * 25_165_824)
    # stage 1's weights are 655 KB a layer
    assert 2 * 10 * 128 * 128 * 2 == 655_360
    per_layer = 332_800 * 2 + 64 * 2 * 5376 + 64 * (2 * 1280 + 128 + 256) * 2
    assert counts.cca_conv_bytes(f, 64, 64) == 16 * per_layer
    live = 64 * 1800.0
    whole = counts.decode_step_bytes(f, live, live_slots=64)
    assert whole == pytest.approx(2 * want + live * 16_384
                                  + 16 * 64 * 2 * 5376)
    assert whole / 1e9 == pytest.approx(9.6, abs=.1)
    # 15.7 of 16 experts a layer touched: the idle ones are not read
    assert counts.decode_step_bytes(f, live, live_slots=64,
                                    experts_touched=16 * 15.7) \
        == pytest.approx(whole - 16 * 0.3 * 25_165_824)
    # the head is 11% of a decode step's bytes at 16 layers
    assert 2 * 262_272 * 2048 / whole == pytest.approx(0.11, abs=.01)
    assert counts.paged_decode_attention_flops(f, live) \
        == 16 * live * 4 * 8 * 128
    assert counts.paged_decode_attention_bytes(f, live, 64) \
        == 16 * (live * 1024 + 64 * 8 * 128 * 6)
    assert counts.model_flops_per_token(f, 0) == pytest.approx(
        2 * (16 * (2048 * 1536 + 1024 * 2048 + 327_680 + 659_984
                   + 12_582_912) + 262_272 * 2048))


def test_counts_are_the_programs_own(cfg_file, counts):
    import math

    import jax
    from distributed_training_sandbox_tpu.models import cca_moe as C
    from distributed_training_sandbox_tpu.models import transformer as T
    from distributed_training_sandbox_tpu.serving import kv_pool
    f = cfg_file["fields"]
    mcfg = harness.model_config(f)
    assert mcfg.cca_moe and mcfg.param_count() == counts.param_count(f)
    assert mcfg.intermediate_size is None
    shapes = jax.eval_shape(lambda k: T.init_params(k, mcfg),
                            jax.random.key(0))
    assert sum(math.prod(x.shape) for x in jax.tree.leaves(shapes)) \
        == counts.param_count(f)
    tiny = {**f, **cfg_file["rehearse"]["fields"]}
    assert harness.model_config(tiny).param_count() \
        == counts.param_count(tiny)
    assert math.prod(C.tail_shape(mcfg)) * 2 == counts.tail_bytes(f)
    assert kv_pool.slot_state_bytes(mcfg) == 16 * counts.tail_bytes(f)
    assert kv_pool.paged_layers(mcfg) == 16 and not kv_pool.slab_pool(mcfg)
    assert kv_pool.paged_layers(mcfg) * kv_pool.token_row_bytes(mcfg) \
        == counts.kv_bytes_per_token(f)
    # the memory the cell fills: weights + pages + tails, of 16.9 GB
    t = json.loads(TRAFFIC.read_text())["engine"]
    pages = t["max_batch"] * t["max_seq_len"] // t["page_size"] + 1
    held = 2 * counts.param_count(f) \
        + t["max_batch"] * kv_pool.slot_state_bytes(mcfg) \
        + pages * t["page_size"] * counts.kv_bytes_per_token(f)
    assert pages == 16_385 and round(held / 1e9, 1) == 12.0


# ------------------------------------------------------------- the scopes

def test_the_engine_opens_the_catalogues_scopes_for_this_block():
    """Lowered at the rehearsal's size with debug info: the block's programs
    carry ``cca_conv`` beneath ``attn_qkv``, the expert layer's names but
    the shared expert's, and ``attn_paged`` beneath ``attn_core``."""
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
        "moe_expert_layer_steps", "conv_tail_slot_steps")
    dec = E.make_serve_decode_step(mcfg).trace(
        bufs, params, i32(B, P), i32(B), i32(B), i32(B), sd((B,), jnp.bool_),
        i32(5)).lower().as_text(debug_info=True)
    pre = E.make_serve_prefill_step(mcfg).trace(
        bufs, params, i32(1, P), i32(1, chunk), i32(), i32(),
        i32()).lower().as_text(debug_info=True)
    for text in (dec, pre):
        assert "attn_qkv/cca_conv/" in text
        assert "mlp/moe_route/" in text and "mlp/moe_experts/" in text
        assert "/moe_shared/" not in text and "/lin_" not in text
        assert "attn_core/attn_paged/" in text
    assert CS.CCA_SUBSCOPES == profiling.CCA_SUBSCOPES == ("cca_conv",)
    assert not set(CS.CCA_SUBSCOPES) & set(
        profiling.SCOPES + profiling.SUBSCOPES + profiling.LINEAR_SUBSCOPES
        + profiling.ATTENTION_SUBSCOPES + profiling.WINDOW_SUBSCOPES)
    assert CS.innermost("jit(f)/attn_qkv/cca_conv/mul") == "cca_conv"
    assert CS.innermost("jit(f)/attn_qkv/dot_general") is None


def test_the_cca_scope_table_books_self_time_under_the_name_alone():
    """``_ccascopes.reduce`` on a hand-made trace: ops under ``cca_conv``
    are booked to it by program, an op that nests another counts its own
    time once, ops under other names are left out."""
    from benchmarks.layer_metrics import _scopes as S
    ops = [("fusion.1", 100.0, 40.0, "jit(step)/attn_qkv/cca_conv/mul"),
           ("fusion.2", 110.0, 10.0, "jit(step)/attn_qkv/cca_conv/add"),
           ("fusion.3", 150.0, 30.0, "jit(step)/attn_qkv/dot"),
           ("fusion.4", 300.0, 20.0, "jit(step)/attn_core/attn_paged/x"),
           ("fusion.5", 1100.0, 7.0, "jit(other)/attn_qkv/cca_conv/mul")]
    raw = S.ScopedRaw(devices={0: {
        "ops": ops, "modules": [("jit_step(1)", 90.0, 400.0),
                                ("jit_other(2)", 1000.0, 200.0)]}})
    got = CS.reduce(raw, (0.0, 2000.0))
    assert {k: round(v, 6) for k, v in got.items()} == {
        (AS.R.module_group("jit_step(1)"), "cca_conv"): 40.0,
        (AS.R.module_group("jit_other(2)"), "cca_conv"): 7.0}


# -------------------------------------------------------------- the readers

def _ctx(counts, fields, stats, **counters):
    return SimpleNamespace(
        trace=None, fields=fields, counts=counts,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        counters={"stats": stats, "engine": {"max_batch": 64}, **counters})


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
    assert (mod.MOVES, mod.RUNNERS) == ("serve_tokens_per_s", ("serve",))
    assert mod.UNIT == ("ms" if name.endswith("_ms_tput") else "%")


def test_the_readers_arithmetic(monkeypatch, cfg_file, counts):
    f = cfg_file["fields"]
    stats = {"rounds": 10, "decode_steps": 40, "occupancy_sum": 600,
             "moe_assignments": 40 * 16 * 60,
             "moe_assignments_held": 40 * 16 * 60,
             "moe_experts_touched": 40 * 16 * 15,
             "conv_tail_slot_steps": 40 * 60, "prefill_chunks": 20,
             "prefill_head_chunks": 5}
    ctx = _ctx(counts, f, stats, kv_valid_sum=10 * 60 * 1800, kv_samples=10)
    monkeypatch.setattr(
        CS, "subscope_ms_per_launch",
        lambda ctx, names, label: {(("cca_conv",), "decode"): 0.5}.get(
            (names, label)))
    monkeypatch.setattr(
        SS, "subscope_ms_per_launch",
        lambda ctx, names, label: {(("moe_experts",), "decode"): 9.0,
                                   (("moe_route",), "decode"): 0.7}.get(
            (names, label)))
    monkeypatch.setattr(
        AS, "subscope_ms_per_launch",
        lambda ctx, names, label: {(("attn_paged",), "decode"): 2.5}.get(
            (names, label)))
    read = lambda name: harness.find_module(  # noqa: E731
        "layer_metrics", name).read(ctx)
    assert read("cca_conv_ms_tput") == 0.5
    # 60 live slots: weights 10.6 MB, tails 10.3 MB, rows 5.7 MB
    least = 16 * (332_800 * 2 + 60 * 2 * 5376 + 60 * 2944 * 2) / 819e9
    assert read("cca_conv_roofline_tput") == pytest.approx(
        100 * least / 0.5e-3)
    assert 5 < read("cca_conv_roofline_tput") < 8
    assert read("moe_held_share_tput") == 100.0
    # the accepted readers the cell joins count with THIS block's counts
    assert read("moe_experts_roofline_tput") == pytest.approx(
        100 * 16 * 15 * 25_165_824 / 819e9 / 9e-3)
    assert 80 < read("moe_experts_roofline_tput") < 84
    assert read("moe_tokens_per_expert_tput") == 4
    assert read("moe_route_ms_tput") == 0.7 and read("paged_attn_ms_tput") == 2.5
    live, slots = 60 * 1800.0, 60.0
    assert read("paged_attn_roofline_tput") == pytest.approx(
        100 * 16 * (live * 1024 + slots * 8 * 128 * 6) / 819e9 / 2.5e-3)
    assert read("prefill_head_share_tput") == 25.0
    # a cell that holds one rank's share: cell 6's 8 of 256
    stats6 = {**stats, "moe_assignments_held": 40 * 16 * 60 // 32}
    ctx6 = _ctx(counts, f, stats6)
    assert harness.find_module("layer_metrics", "moe_held_share_tput").read(
        ctx6) == pytest.approx(3.125)


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
    assert check["reference"] == "benchmarks/reference/cca_moe.py"
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
    # model the tied head returns the token before it unless the layers
    # outweigh its embedding, and a fault has few tokens to show in
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
    assert s["conv_tail_slot_steps"] > 0 and s["admitted"] > 0
    assert s["moe_expert_layer_steps"] == 3 * s["decode_steps"]
    # every expert of the router is held: every choice is a visit
    assert 0 < s["moe_experts_touched"] <= s["moe_assignments_held"] \
        == s["moe_assignments"] == 3 * s["conv_tail_slot_steps"]
    assert (s["decode_inplace_steps"] > 0) == kernel
    assert (s["prefill_inplace_chunks"] > 0) == kernel


#: the faults that move a served token of the 64-wide float32 rehearsal,
#: and the control: the program over int8 operands in every product
MOVES_TOKENS = ("shifted_half_from_the_current_token",
                "tail_zeroed_at_a_chunk_boundary", "temperature_left_out",
                "top1_weight_renormalised", "every_product_in_int8")


@pytest.mark.parametrize("fault", MOVES_TOKENS)
def test_a_planted_fault_is_not_correct(fault):
    """A fault of ``cca_moe_faults`` moves served tokens off the
    reference's argmax by more than the tight limits allow, with nothing
    else failing: no request is lost, nothing recompiles.  (int8 in the
    two projections alone is read on the chip: the configuration's
    ``check.why``.)"""
    from tests.benchmark import cca_moe_faults
    obs = _drive(TIGHT, cca_moe_faults.FAULTS[fault][0])
    check = obs["check"]
    assert check["ok"] is False and obs["correct"] is False, check
    assert check["retraces_after_warmup"] == 0
    assert check["gap_sigma_mean"] > TIGHT["gap_sigma_mean"]


def test_the_int8_control_lowers_the_program_and_not_the_cells_fields():
    from tests.benchmark import cca_moe_faults
    fields = json.loads(CONFIG.read_text())["fields"]
    plant, names = cca_moe_faults.FAULTS["matmuls_in_int8"]
    assert names == "both"
    with plant():
        assert harness.model_config(fields).matmul_precision == "int8"
    assert harness.model_config(fields).matmul_precision == "bf16"


@pytest.mark.parametrize("fault,programs", [
    (name, programs) for name, (_, programs) in sorted(
        __import__("tests.benchmark.cca_moe_faults",
                   fromlist=["FAULTS"]).FAULTS.items())
    if name != "matmuls_in_int8"])
def test_a_fault_changes_the_programs_it_says(fault, programs):
    """Lowered at the rehearsal's size: a decode-step fault leaves the
    prefill program's StableHLO as it was, the chunk boundary's fault the
    decode program's, and the control in int8 changes both."""
    import jax
    import jax.numpy as jnp
    from distributed_training_sandbox_tpu.models import transformer as T
    from distributed_training_sandbox_tpu.serving import engine as E
    from distributed_training_sandbox_tpu.serving.kv_pool import PagedKVPool
    from tests.benchmark import cca_moe_faults
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
    with cca_moe_faults.FAULTS[fault][0]():
        faulty = texts()
    for program in ("decode", "prefill"):
        assert (faulty[program] != sound[program]) \
            == (programs in (program, "both"))
