"""The ``gdn_moe`` architecture's benchmark files: the counts pinned to a
hand count of the cut, the configuration against the catalog's numbers, the
cell's eighteen readers, the new subscope's helper on a hand-made trace,
the three new readers, the planted faults, and the new cell's rehearsal.
CPU only: counts and control flow, no device metric."""

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
from benchmarks.layer_metrics import _linscopes as LS  # noqa: E402
from benchmarks.layer_metrics import _scopes as S  # noqa: E402
from benchmarks.layer_metrics import _subscopes as SS  # noqa: E402

CELL = "serve-hybrid-moe-longgen"
CONFIG = ROOT / "benchmarks/configs/qwen3-next-80b-ep16-l24-serve.json"
TRAFFIC = ROOT / "benchmarks/workloads/reasoning-backlog.json"
#: Qwen3-Next-80B-A3B-Instruct's published config.json, the numbers
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "moe_intermediate_size": 512, "num_attention_heads": 16,
    "num_experts": 512, "num_experts_per_tok": 10, "num_hidden_layers": 48,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "vocab_size": 151936}
AS_RUN = {"num_hidden_layers": 24, "num_experts": 32}
SHARED = ("decode_ms_per_step_tput", "prefill_ms_per_chunk_tput",
          "decode_attn_ms_tput", "prefill_attn_ms_tput",
          "engine_batch_occupancy_tput", "sched_host_ms_per_round_tput",
          "serve_device_idle_pct_tput", "decode_inplace_share_tput")
MOE = ("moe_experts_ms_tput", "moe_experts_roofline_tput",
       "moe_tokens_per_expert_tput")
LIN = ("lin_step_ms_tput", "lin_step_roofline_tput", "lin_scan_ms_tput",
       "lin_scan_roofline_tput")
NEW_READERS = ("paged_attn_ms_tput", "paged_attn_roofline_tput",
               "moe_route_ms_tput")


@pytest.fixture(scope="module")
def cfg_file():
    return json.loads(CONFIG.read_text())


@pytest.fixture(scope="module")
def counts():
    return harness.find_module("counts", "gdn_moe")


# ------------------------------------------------------------- the counts

def test_counts_are_the_cuts_arithmetic(cfg_file, counts):
    f = cfg_file["fields"]
    lin = 2048 * 12_288 + 2048 * 64 + 4 * 8_192 + 4_096 * 2_048 \
        + 2 * 32 + 128                                          # 33.72 M
    full = 2048 * 8_192 + 2 * 2048 * 512 + 4_096 * 2_048 + 2 * 256
    rest = 2048 * 512 + 3 * 2048 * 512 + 2048 + 2 * 2048        # 4.20 M
    expert = 3 * 2048 * 512
    assert counts.linear_layer_weight_count(f) == lin == 33_718_464
    assert counts.full_layer_weight_count(f) == full == 27_263_488
    assert counts.moe_rest_weight_count(f) == rest == 4_200_448
    assert counts.expert_weight_count(f) == expert == 3_145_728
    assert counts.conv_channels(f) == 8_192
    want = 18 * (lin + rest + 32 * expert) + 6 * (full + rest + 32 * expert) \
        + 2 * 151_936 * 2048 + 2048
    assert counts.param_count(f) == want == 3_909_575_040
    assert round(2 * want / 1e9, 2) == 7.82
    # all 512 experts of a layer: the 3.22 GB that no chip holds five times
    assert round(2 * (512 * expert + rest + lin) / 1e9, 2) == 3.3
    assert counts.kv_bytes_per_token(f) == 6 * 2 * 2 * 256 * 2 == 12_288
    assert counts.state_bytes(f) == 32 * 128 * 128 * 4 == 2_097_152
    assert counts.slot_state_bytes(f) == 2_097_152 + 3 * 8_192 * 2
    # the recurrence moves the STATE alone: the tail is the conv's
    assert counts.state_step_bytes(f, 64) == 18 * 64 * 2 * 2_097_152
    assert counts.state_step_bytes(f, 64) / 1e9 == pytest.approx(4.83, abs=.01)
    assert counts.expert_step_bytes(f, 24 * 32) / 1e9 == pytest.approx(
        4.83, abs=.01)
    live = 64 * 1300.0
    assert counts.decode_step_bytes(f, live, live_slots=64) == pytest.approx(
        2 * (want - 151_936 * 2048) + live * 12_288
        + 18 * 64 * 2 * (2_097_152 + 49_152))
    assert counts.decode_step_bytes(f, live, live_slots=64) / 1e9 \
        == pytest.approx(13.2, abs=.1)
    # 23 of 32 experts a layer touched: the idle ones' weights are not read
    assert counts.decode_step_bytes(f, live, live_slots=64,
                                    experts_touched=24 * 23) \
        == pytest.approx(counts.decode_step_bytes(f, live, live_slots=64)
                         - 24 * 9 * expert * 2)
    # paged decode attention: memory bound by a factor of 30
    assert counts.paged_decode_attention_flops(f, live) \
        == 6 * live * 4 * 16 * 256
    assert counts.paged_decode_attention_bytes(f, live, 64) \
        == 6 * (live * 2 * 2 * 256 * 2 + 64 * 16 * 256 * 6)
    assert counts.paged_decode_attention_bytes(f, live, 64) / 819e9 \
        > 20 * counts.paged_decode_attention_flops(f, live) / 197e12
    # the chunked scan at sub-chunks of 64: the Gram matrices a key head,
    # the rest a value head
    per_key = 4 * 64 * 64 * 128
    per_value = 64 * 64 * (2 * 128 + 4 * 128) + 64 ** 3 + 6 * 64 * 128 * 128
    assert counts.chunk_scan_flops(f, 256) \
        == 18 * 4 * (16 * per_key + 32 * per_value)
    assert counts.chunk_scan_flops(f, 96) \
        == 18 * 1.5 * (16 * per_key + 32 * per_value)
    assert counts.chunk_scan_bytes(f, 256) == 18 * (
        16 * 256 * 2 * 128 * 2
        + 32 * (256 * (2 * 128 * 2 + 8) + 2 * 128 * 128 * 4))
    # with one value head a key head it is the older hybrid's count
    old = harness.find_module("counts", "gdn_hybrid")
    same = {**f, "linear_num_value_heads": 16}
    assert counts.chunk_scan_flops(same, 320) == old.chunk_scan_flops(same, 320)
    assert counts.chunk_scan_bytes(same, 320) == old.chunk_scan_bytes(same, 320)


def test_counts_are_the_programs_own(cfg_file, counts):
    import math

    import jax
    from distributed_training_sandbox_tpu.models import gdn_hybrid as G
    from distributed_training_sandbox_tpu.models import transformer as T
    from distributed_training_sandbox_tpu.serving import kv_pool
    f = cfg_file["fields"]
    mcfg = harness.model_config(f)
    assert mcfg.gdn_moe and mcfg.param_count() == counts.param_count(f)
    shapes = jax.eval_shape(lambda k: T.init_params(k, mcfg),
                            jax.random.key(0))
    assert sum(math.prod(x.shape) for x in jax.tree.leaves(shapes)) \
        == counts.param_count(f)
    tiny = {**f, **cfg_file["rehearse"]["fields"]}
    assert harness.model_config(tiny).param_count() \
        == counts.param_count(tiny)
    assert G.slot_state_bytes(mcfg) == counts.slot_state_bytes(f)
    assert kv_pool.slot_state_bytes(mcfg) == 18 * counts.slot_state_bytes(f)
    assert kv_pool.paged_layers(mcfg) == 6
    assert G.SCAN_CHUNK == counts.SCAN_CHUNK == 64
    # what the pool holds a token is what the counts say: no padded heads
    assert kv_pool.paged_layers(mcfg) * kv_pool.token_row_bytes(mcfg) \
        == 12_288 == counts.kv_bytes_per_token(f)
    assert kv_pool.slab_pool(mcfg)
    # the memory the cell fills: weights + state slots + pages, of 16.9 GB
    held = 2 * counts.param_count(f) + 64 * kv_pool.slot_state_bytes(mcfg) \
        + 16_385 * 16 * 12_288
    assert round(held / 1e9, 1) == 13.5


# ---------------------------------------------------------- the data files

def test_config_file_states_the_cut_and_keeps_every_published_width(
        cfg_file):
    f, fields = cfg_file, cfg_file["fields"]
    assert {k: v for k, v in f["published"].items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)} \
        == PUBLISHED
    assert f["reduced"] == ["num_hidden_layers", "num_experts"]
    for k, v in f["published"].items():
        # the top level of the file is the published config AS RUN
        assert f[k] == AS_RUN.get(k, v), k
        if k in fields:
            assert fields[k] == AS_RUN.get(k, v), k
    assert fields["router_width"] == 512 and fields["expert_offset"] == 0
    assert fields["num_experts_per_tok"] == 10
    assert fields["num_hidden_layers"] % fields["full_attention_interval"] == 0
    assert f["published"]["mlp_only_layers"] == []
    assert "no layer uses" in f["reduced_how"]           # intermediate_size
    assert f["architecture"] == "gdn_moe" and f["runner"] == "serve"
    d = f["deployment"]
    assert (d["chips"], d["chips_sharing_a_layer"]) == (1, 16)
    assert d["experts_held_here"] == [0, 31] and d["layers_held_here"] == 24
    assert f["state"]["dtype"] == "float32"
    assert f["state"]["bytes_per_slot_per_linear_layer"] == 2_097_152
    assert {"rotary_pairing", "norms", "linear_mixer", "weights"} \
        <= set(f["assumed"])
    assert "mtp" in f["not_run"]
    assert set(f["check"]) == {"gap_sigma_mean", "gap_sigma_max", "why"}
    # two engine arguments off their defaults, with the runs they were
    # chosen on
    assert f["serve"]["engine"] == {"prefill_chunks_per_round": 12,
                                    "sync_every": 16}
    assert "30 s" in f["serve"]["engine_why"]
    assert fields["dtype"] == "bfloat16"
    r = f["rehearse"]["fields"]
    assert r["num_hidden_layers"] == 4 and r["linear_num_value_heads"] \
        == 2 * r["linear_num_key_heads"]


def test_the_traffic_file_is_the_accepted_one():
    """The cell runs under cell 6's traffic file, which this PR leaves as
    it was."""
    t = json.loads(TRAFFIC.read_text())
    assert t["params"]["arrival"] == {"process": "backlog", "count": 320}
    assert t["params"]["max_total"] == 2816
    assert t["engine"] == {"max_batch": 64, "max_seq_len": 4096,
                           "page_size": 16, "prefill_chunk": 256}
    assert t["trace"] == {"seconds": 6.0} and t["check"]["requests"] == 2
    cells = [w["name"] for w in harness.load_benchmark()["workloads"]
             if w["traffic"] == "reasoning-backlog"]
    assert cells[:2] == ["serve-mla-moe-longgen", CELL]


def test_the_cell_reports_its_eighteen_readers():
    cell = harness.load_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "qwen3-next-80b-ep16-l24-serve", "reasoning-backlog", 1)
    assert len(cell.why) <= 200 and "16x under" in cell.why
    assert "not engine defaults" in cell.why
    assert [m.name for m in cell.end_to_end] == ["serve_tokens_per_s"]
    names = {m.name for m in cell.per_layer}
    assert names >= {*SHARED, *MOE, *LIN, *NEW_READERS}
    counts = harness.cell_counts(cell)
    assert Path(counts.__file__).name == "gdn_moe.py"
    bm = harness.load_benchmark()
    for entry in bm["per_layer"]:
        if entry["name"] in NEW_READERS:
            assert entry["workloads"][0] == CELL
            assert entry["moves"] == "serve_tokens_per_s"
    # a cell with no expert layer has no router to time
    for other in ("serve-doc-batch", "serve-hybrid-rollout"):
        assert not any(m.name.startswith("moe_")
                       for m in harness.load_cell(other).per_layer)


# ---------------------------------------------------------- the new names

def test_the_helpers_names_are_the_programs():
    from distributed_training_sandbox_tpu.utils import profiling
    assert AS.ATTENTION_SUBSCOPES == profiling.ATTENTION_SUBSCOPES
    assert not set(AS.ATTENTION_SUBSCOPES) & (
        set(S.CATALOGUE) | set(SS.SUBSCOPES) | set(LS.LINEAR_SUBSCOPES))


@pytest.mark.parametrize("path,want,above", [
    ("jit(<unknown>)/attn_core/attn_paged/jit(_decode_float)/reshape",
     "attn_paged", "attn_core"),
    ("jit(<unknown>)/attn_core/attn_paged/jit(_prefill_float)/transpose",
     "attn_paged", "attn_core"),
    ("jit(<unknown>)/attn_core/lin_step/jit(_step)/mul", None, "attn_core"),
    ("jit(<unknown>)/kv_write/scatter", None, "kv_write"),
    ("jit(<unknown>)/attn_core/attn_paged_x/add", None, "attn_core"),
    ("", None, None), (None, None, None)])
def test_innermost_attention_subscope(path, want, above):
    assert AS.innermost(path) == want
    assert S.innermost(path) == above       # the catalogue's reader's name


def test_the_engine_opens_the_scope_for_this_block_alone():
    """Lowered at the rehearsal's size with debug info: the new block's
    programs carry ``attn_core/attn_paged`` round their paged attention and
    the expert layer's three subscopes; the older hybrid's carry neither."""
    import jax
    import jax.numpy as jnp
    from distributed_training_sandbox_tpu.models import transformer as T
    from distributed_training_sandbox_tpu.serving import engine as E
    from distributed_training_sandbox_tpu.serving.kv_pool import PagedKVPool
    B, P, page = 4, 8, 8
    sd = jax.ShapeDtypeStruct
    i32 = lambda *shape: sd(shape, jnp.int32)  # noqa: E731

    def decode_text(name, n_counted):
        f = json.loads((ROOT / f"benchmarks/configs/{name}.json").read_text())
        mcfg = harness.model_config({**f["fields"], **f["rehearse"]["fields"]})
        params = jax.eval_shape(lambda: T.init_params(jax.random.key(0),
                                                      mcfg))
        bufs = jax.eval_shape(
            lambda: PagedKVPool(mcfg, B * P + 1, page, n_slots=B).bufs)
        return E.make_serve_decode_step(mcfg).trace(
            bufs, params, i32(B, P), i32(B), i32(B), i32(B),
            sd((B,), jnp.bool_), i32(n_counted)).lower().as_text(
                debug_info=True)

    new = decode_text("qwen3-next-80b-ep16-l24-serve", 5)
    assert "attn_core/attn_paged/" in new and "kv_write/attn_paged" not in new
    for name in ("moe_route", "moe_experts", "moe_shared", "lin_step",
                 "lin_conv"):
        assert f"/{name}/" in new, name
    old = decode_text("olmo-hybrid-7b-l12-serve", 1)
    assert "attn_paged" not in old and "moe_route" not in old
    assert "/lin_step/" in old


def test_self_time_per_program_on_a_small_trace():
    us = 1e3
    decode, prefill = "jit__unknown(7)", "jit__unknown(9)"
    ops = [
        # decode launch 0..400: the kernel's call (100) nests a 30 transpose
        # under the same name; a lin_step op and a kv_write op are not counted
        ("custom-call.1", 10 * us, 100 * us,
         "jit(<unknown>)/attn_core/attn_paged/jit(_decode_float)/call"),
        ("copy.2", 20 * us, 30 * us,
         "jit(<unknown>)/attn_core/attn_paged/jit(_decode_float)/transpose"),
        ("fusion.3", 200 * us, 50 * us, "jit(<unknown>)/attn_core/lin_step/r"),
        ("fusion.4", 300 * us, 40 * us, "jit(<unknown>)/kv_write/scatter"),
        # prefill launch 500..900, cut by the window's end at 700
        ("custom-call.5", 650 * us, 100 * us,
         "jit(<unknown>)/attn_core/attn_paged/jit(_prefill_float)/call"),
    ]
    raw = S.ScopedRaw(devices={"/device:TPU:0": {
        "ops": ops, "modules": [(decode, 0.0, 400 * us),
                                (prefill, 500 * us, 400 * us)]}})
    got = AS.reduce(raw, (0.0, 700 * us))
    assert got == pytest.approx({(decode, "attn_paged"): 100 * us,
                                 (prefill, "attn_paged"): 50 * us})


# -------------------------------------------------------------- the readers

def _ctx(counts, fields, stats, **counters):
    return SimpleNamespace(
        trace=None, fields=fields, counts=counts,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        counters={"stats": stats, "engine": {"max_batch": 64}, **counters})


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_program_without_the_names_gives_the_new_readers_nothing(
        name, cfg_file, counts):
    """The parent's engine has neither the scope nor this block, and an
    untraced run has no table: each reader returns None, never raises."""
    mod = harness.find_module("layer_metrics", name)
    old = {"rounds": 9, "decode_steps": 36, "occupancy_sum": 50,
           "prefill_chunks": 4}
    assert mod.read(_ctx(counts, cfg_file["fields"], old)) is None
    assert mod.read(_ctx(counts, cfg_file["fields"], old, kv_valid_sum=9000,
                         kv_samples=9)) is None
    assert (mod.MOVES, mod.RUNNERS) == ("serve_tokens_per_s", ("serve",))
    assert mod.LAYER == ("model step" if name.startswith("moe_")
                         else "kernels")
    assert mod.UNIT == ("%" if "roofline" in name else "ms")


def test_the_new_readers_arithmetic(monkeypatch, cfg_file, counts):
    f = cfg_file["fields"]
    stats = {"rounds": 10, "decode_steps": 40, "occupancy_sum": 500}
    ctx = _ctx(counts, f, stats, kv_valid_sum=10 * 50 * 1000, kv_samples=10)
    monkeypatch.setattr(
        AS, "subscope_ms_per_launch",
        lambda ctx, names, label: {(("attn_paged",), "decode"): 1.5}.get(
            (names, label)))
    monkeypatch.setattr(
        SS, "subscope_ms_per_launch",
        lambda ctx, names, label: {(("moe_route",), "decode"): 2.5}.get(
            (names, label)))
    paged = harness.find_module("layer_metrics", "paged_attn_ms_tput")
    route = harness.find_module("layer_metrics", "moe_route_ms_tput")
    assert paged.read(ctx) == 1.5 and route.read(ctx) == 2.5
    roof = harness.find_module("layer_metrics", "paged_attn_roofline_tput")
    # 50 slots x 1,000 positions x 6 layers x 2,048 B + the slots' q and o
    least = 6 * (50_000 * 2_048 + 50 * 16 * 256 * 6) / 819e9
    assert roof.read(ctx) == pytest.approx(100 * least / 1.5e-3)
    assert 45 < roof.read(ctx) < 55
    # the accepted readers the cell joins count with THIS block's counts
    stats.update({"moe_experts_touched": 40 * 24 * 16,
                  "moe_assignments_held": 40 * 24 * 20,
                  "state_slot_steps": 40 * 50, "prefill_chunks": 12,
                  "lin_scan_rows": 12 * 200})
    monkeypatch.setattr(
        SS, "subscope_ms_per_launch",
        lambda ctx, names, label: {(("moe_experts",), "decode"): 7.0}.get(
            (names, label)))
    monkeypatch.setattr(
        LS, "subscope_ms_per_launch",
        lambda ctx, names, label: {(("lin_step",), "decode"): 6.0,
                                   (("lin_scan",), "prefill"): 12.0}.get(
            (names, label)))
    read = lambda name: harness.find_module(  # noqa: E731
        "layer_metrics", name).read(ctx)
    # half the held experts touched: 2.42 GB of 7 ms at the peak = 42%
    assert read("moe_experts_roofline_tput") == pytest.approx(
        100 * 24 * 16 * 3_145_728 * 2 / 819e9 / 7e-3)
    assert read("moe_tokens_per_expert_tput") == 1.25
    # 50 live slots x 18 layers x 2 x 2 MiB: the state alone, so under 100
    assert read("lin_step_roofline_tput") == pytest.approx(
        100 * 18 * 50 * 2 * 2_097_152 / 819e9 / 6e-3)
    assert 70 < read("lin_step_roofline_tput") < 80
    assert 0 < read("lin_scan_roofline_tput") < 5


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
    assert check["reference"] == "benchmarks/reference/gdn_moe.py"
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
    # sharper attention and closer logits than the cell's own scale: at the
    # rehearsal's 64-wide model a fault has few tokens to show in
    cell.config["serve"]["param_scale"] = 5.0
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
    assert s["moe_expert_layer_steps"] == 4 * s["decode_steps"]
    assert 0 < s["moe_experts_touched"] <= s["moe_assignments_held"] \
        <= s["moe_assignments"] == 3 * 4 * s["state_slot_steps"]
    assert (s["decode_inplace_steps"] > 0) == kernel
    assert (s["lin_step_inplace_steps"] == s["decode_steps"]) == kernel
    assert (s["prefill_inplace_chunks"] > 0) == kernel


@pytest.mark.parametrize("fault", [
    "beta_with_the_factor_2", "value_heads_on_the_wrong_key_head",
    "attention_gate_left_out", "rotary_over_the_whole_head",
    "renormalise_over_held", "shared_gate_left_out"])
def test_a_planted_fault_is_not_correct(fault):
    """A fault of ``gdn_moe_faults`` moves served tokens off the
    reference's argmax by more than the tight limits allow, with nothing
    else failing: no request is lost, nothing recompiles.
    (``state_in_bf16`` and int8 projections move no token of a 64-wide
    float32 model; both are held on logits in ``tests/test_gdn_moe.py`` and
    on the chip: the configuration's ``check.why``.)"""
    from tests.benchmark import gdn_moe_faults
    obs = _drive(TIGHT, gdn_moe_faults.FAULTS[fault][0])
    check = obs["check"]
    assert check["ok"] is False and obs["correct"] is False, check
    assert check["retraces_after_warmup"] == 0
    assert check["gap_sigma_mean"] > TIGHT["gap_sigma_mean"]


def test_the_int8_control_lowers_the_program_and_not_the_cells_fields():
    """``matmuls_in_int8`` builds the PROGRAM's config in int8; the fields
    the reference is given stay the cell's."""
    from tests.benchmark import gdn_moe_faults
    fields = json.loads(CONFIG.read_text())["fields"]
    plant, names = gdn_moe_faults.FAULTS["matmuls_in_int8"]
    assert names == "both"
    with plant():
        assert harness.model_config(fields).matmul_precision == "int8"
    assert harness.model_config(fields).matmul_precision == "bf16"
    assert fields["matmul_precision"] == "bf16"


@pytest.mark.parametrize("fault", [
    "state_in_bf16", "beta_with_the_factor_2",
    "value_heads_on_the_wrong_key_head", "attention_gate_left_out",
    "rotary_over_the_whole_head", "renormalise_over_held",
    "shared_gate_left_out"])
def test_a_fault_changes_the_decode_program_and_not_the_prefill(fault):
    """Lowered at the rehearsal's size: every fault of the file is a
    decode-step fault, so the prefill program's StableHLO is as it was."""
    import jax
    import jax.numpy as jnp
    from distributed_training_sandbox_tpu.models import transformer as T
    from distributed_training_sandbox_tpu.serving import engine as E
    from distributed_training_sandbox_tpu.serving.kv_pool import PagedKVPool
    from tests.benchmark import gdn_moe_faults
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
    plant, names = gdn_moe_faults.FAULTS[fault]
    assert names == "decode"
    with plant():
        faulty = texts()
    assert faulty["decode"] != sound["decode"]
    assert faulty["prefill"] == sound["prefill"]
