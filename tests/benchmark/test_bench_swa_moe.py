"""The ``swa_moe`` architecture's benchmark files: the counts pinned to a
hand count of the cut, the configuration against the catalog's numbers, the
traffic file, the cell's eleven first readers, the new subscope's helper
on a hand-made trace, the six readers of its window layers and page
classes (entries of ``BENCHMARK.json`` since PR 46), the planted faults,
and the new cell's rehearsal.  CPU only: counts and control
flow, no device metric."""

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
from benchmarks.layer_metrics import _winscopes as WS  # noqa: E402

CELL = "serve-swa-moe-mixedlen"
CONFIG = ROOT / "benchmarks/configs/trinity-large-ep32-l5-serve.json"
TRAFFIC = ROOT / "benchmarks/workloads/mixed-length-backlog.json"
#: Trinity-Large-Preview's published config.json, the numbers
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_size": 3072,
    "intermediate_size": 12288, "load_balance_coeff": 5e-05,
    "max_position_embeddings": 262144, "moe_intermediate_size": 3072,
    "n_group": 1, "num_attention_heads": 48, "num_dense_layers": 6,
    "num_expert_groups": 1, "num_experts": 256, "num_experts_per_tok": 4,
    "num_hidden_layers": 60, "num_key_value_heads": 8,
    "num_limited_groups": 1, "num_shared_experts": 1, "rms_norm_eps": 1e-05,
    "rope_theta": 10000, "route_scale": 2.448, "sliding_window": 4096,
    "topk_group": 1, "vocab_size": 200192}
AS_RUN = {"num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 8}
SHARED = ("decode_ms_per_step_tput", "prefill_ms_per_chunk_tput",
          "decode_attn_ms_tput", "prefill_attn_ms_tput",
          "engine_batch_occupancy_tput", "sched_host_ms_per_round_tput",
          "serve_device_idle_pct_tput", "decode_inplace_share_tput")
MOE = ("moe_experts_ms_tput", "moe_experts_roofline_tput",
       "moe_tokens_per_expert_tput")
NEW_READERS = ("swa_decode_attn_ms_tput", "swa_decode_attn_roofline_tput",
               "swa_prefill_attn_ms_tput", "swa_prefill_attn_roofline_tput",
               "full_decode_attn_roofline_tput", "kv_window_rows_share_tput")


@pytest.fixture(scope="module")
def cfg_file():
    return json.loads(CONFIG.read_text())


@pytest.fixture(scope="module")
def counts():
    return harness.find_module("counts", "swa_moe")


# ------------------------------------------------------------- the counts

def test_counts_are_the_cuts_arithmetic(cfg_file, counts):
    f = cfg_file["fields"]
    attn = 3 * 3072 * 6144 + 2 * 3072 * 1024
    norms = 4 * 3072 + 2 * 128
    expert = 3 * 3072 * 3072
    assert counts.attention_weight_count(f) == attn == 62_914_560
    assert counts.expert_weight_count(f) == expert == 28_311_552
    dense = attn + norms + 3 * 3072 * 12_288
    assert counts.dense_layer_weight_count(f) == dense == 176_173_312
    layer = attn + norms + 3072 * 256 + 256 + 9 * expert
    assert counts.expert_layer_weight_count(f) == layer == 318_517_760
    want = dense + 4 * layer + 2 * 200_192 * 3072 + 3072
    assert counts.param_count(f) == want == 2_680_227_072
    assert round(2 * want / 1e9, 2) == 5.36
    # all 256 experts of a layer: the 14.6 GB that no chip holds
    assert round(2 * (257 * expert + attn) / 1e9, 1) == 14.7
    assert counts.layer_kinds(f) == (4, 1)
    assert counts.kv_row_bytes(f) == 2 * 8 * 128 * 2 == 4_096
    assert counts.kv_bytes_per_token(f) == 5 * 4_096
    assert counts.kv_bytes_per_token(f, kind="window") == 4 * 4_096
    assert counts.kv_bytes_per_token(f, kind="full") == 4_096
    # the pools at the cell's shape: a full layer's 52,225 pages, a window
    # layer's 13,825; held as full layers the five would not fit the chip
    page = 16 * 4_096
    assert round(52_225 * page / 1e9, 2) == 3.42
    assert round(4 * 13_825 * page / 1e9, 2) == 3.62
    assert round(5 * 52_225 * page / 1e9, 1) == 17.1
    assert round((2 * want + (52_225 + 4 * 13_825) * page) / 1e9, 1) == 12.4
    assert counts.expert_step_bytes(f, 32) == 32 * expert * 2
    # a step at 48 slots of the mix's mean context (5.8k prompt + ~256)
    live, seen = 48 * 6_056.0, 48 * 3_300.0
    got = counts.decode_step_bytes(f, live, window_kv_tokens=seen)
    assert got == pytest.approx(
        2 * (want - 200_192 * 3072) + live * 4_096 + seen * 4 * 4_096)
    assert counts.decode_step_bytes(f, live) == pytest.approx(
        2 * (want - 200_192 * 3072) + live * 5 * 4_096)
    assert counts.decode_step_bytes(f, live, window_kv_tokens=seen,
                                    experts_touched=20) \
        == pytest.approx(got - 12 * expert * 2)
    # decode attention: per layer and visible row 4 x 128 FLOPs a query
    # head and the row's 4,096 B once; memory bound by a factor of 10
    assert counts.window_decode_attention_flops(f, seen) \
        == 4 * seen * 4 * 48 * 128
    assert counts.window_decode_attention_bytes(f, seen, 48) \
        == 4 * (seen * 4_096 + 48 * 48 * 128 * 6)
    assert counts.full_decode_attention_flops(f, live) == live * 4 * 48 * 128
    assert counts.full_decode_attention_bytes(f, live, 48) \
        == live * 4_096 + 48 * 48 * 128 * 6
    assert counts.full_decode_attention_bytes(f, live) / 819e9 \
        > 9 * counts.full_decode_attention_flops(f, live) / 197e12
    # a prefill chunk of 512 rows past the window: 512 x 4,096 pairs a
    # layer; compute bound by a factor of 6
    pairs = 512 * 4_096.0
    assert counts.window_prefill_attention_flops(f, pairs) \
        == 4 * pairs * 4 * 48 * 128
    assert counts.window_prefill_attention_bytes(f, pairs, 512) \
        == 4 * (4_096 * 4_096 + 512 * 48 * 128 * 6)
    assert counts.window_prefill_attention_flops(f, pairs) / 197e12 \
        > 6 * counts.window_prefill_attention_bytes(f, pairs, 512) / 819e9


def test_counts_are_the_programs_own(cfg_file, counts):
    import math

    import jax
    from distributed_training_sandbox_tpu.models import transformer as T
    from distributed_training_sandbox_tpu.serving import kv_pool
    f = cfg_file["fields"]
    mcfg = harness.model_config(f)
    assert mcfg.swa_moe and mcfg.param_count() == counts.param_count(f)
    shapes = jax.eval_shape(lambda k: T.init_params(k, mcfg),
                            jax.random.key(0))
    assert sum(math.prod(x.shape) for x in jax.tree.leaves(shapes)) \
        == counts.param_count(f)
    tiny = {**f, **cfg_file["rehearse"]["fields"]}
    assert harness.model_config(tiny).param_count() \
        == counts.param_count(tiny)
    assert kv_pool.paged_layers(mcfg) == 5
    assert kv_pool.token_row_bytes(mcfg) == counts.kv_row_bytes(f)
    assert not kv_pool.slab_pool(mcfg)
    # the cell's two page classes, as the engine sizes them
    eng = json.loads(TRAFFIC.read_text())["engine"]
    ring = kv_pool.ring_pages(mcfg, eng["page_size"], eng["prefill_chunk"])
    assert ring == (4_096 + 512) // 16 == 288
    assert eng["max_batch"] * ring + 1 == 13_825
    assert eng["max_batch"] * eng["max_seq_len"] // eng["page_size"] + 1 \
        == 52_225
    bufs = jax.eval_shape(lambda: kv_pool.PagedKVPool(
        mcfg, 52_225, 16, n_pages_window=13_825).bufs)
    assert [a.shape for a in bufs.k] == [(13_825, 16, 8, 128)] * 3 \
        + [(52_225, 16, 8, 128), (13_825, 16, 8, 128)]
    held = sum(math.prod(a.shape) * 2 for a in bufs.k + bufs.v) \
        + 2 * counts.param_count(f)
    assert round(held / 1e9, 1) == 12.4


# ---------------------------------------------------------- the data files

def test_config_file_states_the_cut_and_keeps_every_published_width(
        cfg_file):
    f, fields = cfg_file, cfg_file["fields"]
    assert {k: v for k, v in f["published"].items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)} \
        == PUBLISHED
    assert f["reduced"] == ["num_hidden_layers", "num_dense_layers",
                            "num_experts"]
    for k, v in f["published"].items():
        # the top level of the file is the published config AS RUN
        assert f[k] == AS_RUN.get(k, v), k
        if k in fields:
            assert fields[k] == AS_RUN.get(k, v), k
    assert len(f["layer_types"]) == 60
    assert f["layer_types"][:5] == ["sliding_attention"] * 3 \
        + ["full_attention", "sliding_attention"]
    assert fields["router_width"] == 256 and fields["expert_offset"] == 0
    assert fields["routed_scaling_factor"] == f["route_scale"] == 2.448
    assert fields["norm_topk_prob"] is f["route_norm"] is True
    assert fields["mup_enabled"] is f["mup_enabled"] is True
    assert f["architecture"] == "swa_moe" and f["runner"] == "serve"
    d = f["deployment"]
    assert (d["chips"], d["chips_sharing_a_layer"]) == (1, 32)
    assert d["experts_held_here"] == [0, 7] and d["layers_held_here"] == 5
    assert {"embedding_scale", "rotary", "attention_gate", "norms",
            "selection_bias", "weights"} <= set(f["assumed"])
    assert f["not_run"] == {}
    assert set(f["check"]) == {"gap_sigma_mean", "gap_sigma_max", "why"}
    # two engine arguments off their defaults, unequal, with the runs they
    # were chosen on
    e = f["serve"]["engine"]
    assert set(e) == {"prefill_chunks_per_round", "sync_every"}
    assert e["prefill_chunks_per_round"] != e["sync_every"]
    assert "chip runs, PR 38" in f["serve"]["engine_why"]
    assert fields["dtype"] == "bfloat16"
    r = f["rehearse"]["fields"]
    assert r["num_hidden_layers"] == 5 and r["sliding_window"] == 16


def test_the_traffic_file_holds_the_issues_parameters():
    t = json.loads(TRAFFIC.read_text())
    assert t["generator"] == "request_stream"
    assert t["params"] == {
        "arrival": {"process": "backlog", "count": 384},
        "prompt_len": {"dist": "lognormal", "median": 4096, "sigma": 1.0,
                       "min": 256, "max": 16384, "stratified": 8},
        "output_len": {"dist": "uniform", "min": 256, "max": 768,
                       "stratified": 8},
        "max_total": 17152}
    assert t["engine"] == {"max_batch": 48, "max_seq_len": 17408,
                           "page_size": 16, "prefill_chunk": 512}
    assert t["drain_s"] == 60.0 and t["trace"] == {"seconds": 10.0}
    assert t["check"] == {"requests": 6, "block": 512}
    # every eight requests hold the whole distribution: half under the
    # window, half up to four times over it
    from benchmarks.traffic._dist import _quantiles
    assert _quantiles(t["params"]["prompt_len"], 8).tolist() \
        == [883, 1687, 2512, 3500, 4794, 6678, 9946, 16384]
    # the rehearsal's window (16) is shorter than its contexts (64), and
    # its ring (16 + 16 rows) shorter still: the CPU run wraps the ring
    r = t["rehearse"]
    assert r["params"]["max_total"] == r["engine"]["max_seq_len"] == 64
    assert r["params"]["prompt_len"]["max"] > 32
    cells = [w["name"] for w in harness.load_benchmark()["workloads"]
             if w["traffic"] == "mixed-length-backlog"]
    assert cells[0] == CELL


def test_the_cell_reports_the_eleven_first_accepted_readers():
    cell = harness.load_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "trinity-large-ep32-l5-serve", "mixed-length-backlog", 1)
    assert len(cell.why) <= 200 and "32x under" in cell.why
    assert "not engine defaults" in cell.why
    assert [m.name for m in cell.end_to_end] == ["serve_tokens_per_s"]
    assert {m.name for m in cell.per_layer} >= {*SHARED, *MOE}
    counts = harness.cell_counts(cell)
    assert Path(counts.__file__).name == "swa_moe.py"


def test_the_cell_reports_the_six_new_readers():
    """The six are entries of ``BENCHMARK.json`` (PR 46; until then files
    beside a pinned list): each says what its module says, the cell lists
    them, its counts module has what they call, and a cell with no window
    layer gains none."""
    entries = {m["name"]: m for m in harness.load_benchmark()["per_layer"]}
    for name in NEW_READERS:
        mod, e = harness.find_module("layer_metrics", name), entries[name]
        assert {k: e[k] for k in e if k != "workloads"} == {
            "name": name, "unit": mod.UNIT, "layer": mod.LAYER,
            "better": "lower" if name.endswith("_ms_tput")
            or name.startswith("kv_") else "higher",
            "source": "program_counter" if name.startswith("kv_")
            else "device_trace", "moves": mod.MOVES}
        assert e["workloads"][0] == CELL
    cell = harness.load_cell(CELL)
    assert {m.name for m in cell.per_layer} >= {*SHARED, *MOE, *NEW_READERS}
    counts = harness.cell_counts(cell)
    assert Path(counts.__file__).name == "swa_moe.py"
    for name in NEW_READERS:
        for need in getattr(harness.find_module("layer_metrics", name),
                            "COUNTS", ()):
            assert hasattr(counts, need), need
    for other in ("serve-doc-batch", "serve-mla-moe-longgen",
                  "serve-hybrid-moe-longgen"):
        assert not set(NEW_READERS) & {
            m.name for m in harness.load_cell(other).per_layer}


# ---------------------------------------------------------- the new names

def test_the_helpers_names_are_the_programs():
    from distributed_training_sandbox_tpu.models import swa_moe
    from distributed_training_sandbox_tpu.utils import profiling
    assert WS.WINDOW_SUBSCOPES == profiling.WINDOW_SUBSCOPES \
        == (swa_moe.WINDOW_ATTENTION_SCOPE,)
    assert swa_moe.PAGED_ATTENTION_SCOPE in AS.ATTENTION_SUBSCOPES
    assert not set(WS.WINDOW_SUBSCOPES) & (
        set(S.CATALOGUE) | set(SS.SUBSCOPES) | set(LS.LINEAR_SUBSCOPES)
        | set(AS.ATTENTION_SUBSCOPES))


@pytest.mark.parametrize("path,want,paged", [
    ("jit(<unknown>)/attn_core/attn_window/jit(_decode_float)/reshape",
     "attn_window", None),
    ("jit(<unknown>)/attn_core/attn_window/jit(_prefill_float)/transpose",
     "attn_window", None),
    ("jit(<unknown>)/attn_core/attn_paged/jit(_decode_float)/call", None,
     "attn_paged"),
    ("jit(<unknown>)/kv_write/scatter", None, None),
    ("jit(<unknown>)/attn_core/attn_window_x/add", None, None),
    ("", None, None), (None, None, None)])
def test_innermost_window_subscope(path, want, paged):
    assert WS.innermost(path) == want
    assert AS.innermost(path) == paged      # the full layers' reader's name


def test_the_engine_opens_both_scopes_for_this_block_alone():
    """Lowered at the rehearsal's size with debug info: the block's programs
    carry ``attn_core/attn_window`` round the window layers' attention and
    ``attn_core/attn_paged`` round the full layer's, neither round a
    ``kv_write``, and the expert layer's three subscopes; the hybrid with
    expert layers carries no ``attn_window``."""
    import jax
    import jax.numpy as jnp
    from distributed_training_sandbox_tpu.models import transformer as T
    from distributed_training_sandbox_tpu.serving import engine as E
    from distributed_training_sandbox_tpu.serving.kv_pool import PagedKVPool
    B, P, page, R_ = 4, 8, 8, 4
    sd = jax.ShapeDtypeStruct
    i32 = lambda *shape: sd(shape, jnp.int32)  # noqa: E731

    def model(name):
        f = json.loads((ROOT / f"benchmarks/configs/{name}.json").read_text())
        mcfg = harness.model_config({**f["fields"], **f["rehearse"]["fields"]})
        return mcfg, jax.eval_shape(lambda: T.init_params(jax.random.key(0),
                                                          mcfg))

    mcfg, params = model("trinity-large-ep32-l5-serve")
    bufs = jax.eval_shape(lambda: PagedKVPool(
        mcfg, B * P + 1, page, n_pages_window=B * R_ + 1).bufs)
    tables = lambda b: (i32(b, P), i32(b, R_))  # noqa: E731
    decode = E.make_serve_decode_step(mcfg).trace(
        bufs, params, tables(B), i32(B), i32(B), i32(B), sd((B,), jnp.bool_),
        i32(6)).lower().as_text(debug_info=True)
    prefill = E.make_serve_prefill_step(mcfg).trace(
        bufs, params, tables(1), i32(1, 16), i32(), i32()).lower().as_text(
            debug_info=True)
    for text in (decode, prefill):
        assert "attn_core/attn_window/" in text
        assert "attn_core/attn_paged/" in text
        assert "kv_write/attn_window" not in text
        assert "attn_window/kv_write" not in text
        for name in ("moe_route", "moe_experts", "moe_shared"):
            assert f"/{name}/" in text, name
    old_cfg, old_params = model("qwen3-next-80b-ep16-l24-serve")
    old_bufs = jax.eval_shape(
        lambda: PagedKVPool(old_cfg, B * P + 1, page, n_slots=B).bufs)
    old = E.make_serve_decode_step(old_cfg).trace(
        old_bufs, old_params, i32(B, P), i32(B), i32(B), i32(B),
        sd((B,), jnp.bool_), i32(5)).lower().as_text(debug_info=True)
    assert "attn_window" not in old and "attn_core/attn_paged/" in old


def test_self_time_per_program_on_a_small_trace():
    us = 1e3
    decode, prefill = "jit__unknown(7)", "jit__unknown(9)"
    ops = [
        # decode launch 0..400: a window layer's kernel call (100) nests a
        # 30 transpose under the same name; the full layer's call and a
        # kv_write op are not counted
        ("custom-call.1", 10 * us, 100 * us,
         "jit(<unknown>)/attn_core/attn_window/jit(_decode_float)/call"),
        ("copy.2", 20 * us, 30 * us,
         "jit(<unknown>)/attn_core/attn_window/jit(_decode_float)/transpose"),
        ("custom-call.3", 200 * us, 50 * us,
         "jit(<unknown>)/attn_core/attn_paged/jit(_decode_float)/call"),
        ("fusion.4", 300 * us, 40 * us, "jit(<unknown>)/kv_write/scatter"),
        # prefill launch 500..900, cut by the window's end at 700
        ("custom-call.5", 650 * us, 100 * us,
         "jit(<unknown>)/attn_core/attn_window/jit(_prefill_float)/call"),
    ]
    raw = S.ScopedRaw(devices={"/device:TPU:0": {
        "ops": ops, "modules": [(decode, 0.0, 400 * us),
                                (prefill, 500 * us, 400 * us)]}})
    got = WS.reduce(raw, (0.0, 700 * us))
    assert got == pytest.approx({(decode, "attn_window"): 100 * us,
                                 (prefill, "attn_window"): 50 * us})
    assert AS.reduce(raw, (0.0, 700 * us)) == pytest.approx(
        {(decode, "attn_paged"): 50 * us})


# -------------------------------------------------------------- the readers

def _ctx(counts, fields, stats, **counters):
    return SimpleNamespace(
        trace=None, fields=fields, counts=counts,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        counters={"stats": stats,
                  "engine": {"max_batch": 48, "prefill_chunk": 512},
                  **counters})


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_program_without_the_names_gives_the_new_readers_nothing(
        name, cfg_file, counts):
    """The parent's engine has neither the scope nor the counters, and an
    untraced run has no table: each reader returns None, never raises."""
    mod = harness.find_module("layer_metrics", name)
    old = {"rounds": 9, "decode_steps": 36, "occupancy_sum": 50,
           "prefill_chunks": 4}
    assert mod.read(_ctx(counts, cfg_file["fields"], old)) is None
    assert mod.read(_ctx(counts, cfg_file["fields"], old, kv_valid_sum=9000,
                         kv_samples=9)) is None
    assert (mod.MOVES, mod.RUNNERS) == ("serve_tokens_per_s", ("serve",))
    assert mod.LAYER == ("scheduler" if name.startswith("kv_")
                         else "kernels")
    assert mod.UNIT == ("ms" if name.endswith("_ms_tput") else "%")


def test_the_new_readers_arithmetic(monkeypatch, cfg_file, counts):
    f = cfg_file["fields"]
    # 8 steps a round at 40 live slots of ~6,000 rows, 3,300 in the window
    stats = {"rounds": 10, "decode_steps": 80, "occupancy_sum": 400,
             "prefill_chunks": 60, "window_rows_read": 80 * 40 * 3_300,
             "full_rows_read": 80 * 40 * 6_000,
             "window_pairs_prefilled": 60 * 512 * 3_000}
    ctx = _ctx(counts, f, stats)
    monkeypatch.setattr(
        WS, "subscope_ms_per_launch",
        lambda ctx, names, label: {(("attn_window",), "decode"): 3.2,
                                   (("attn_window",), "prefill"): 6.0}.get(
            (names, label)))
    monkeypatch.setattr(
        AS, "subscope_ms_per_launch",
        lambda ctx, names, label: {(("attn_paged",), "decode"): 1.6}.get(
            (names, label)))
    read = lambda name: harness.find_module(  # noqa: E731
        "layer_metrics", name).read(ctx)
    assert read("swa_decode_attn_ms_tput") == 3.2
    assert read("swa_prefill_attn_ms_tput") == 6.0
    rows = 40 * 3_300
    assert read("swa_decode_attn_roofline_tput") == pytest.approx(
        100 * 4 * (rows * 4_096 + 40 * 48 * 128 * 6) / 819e9 / 3.2e-3)
    assert 80 < read("swa_decode_attn_roofline_tput") < 90
    assert read("full_decode_attn_roofline_tput") == pytest.approx(
        100 * (40 * 6_000 * 4_096 + 40 * 48 * 128 * 6) / 819e9 / 1.6e-3)
    assert 70 < read("full_decode_attn_roofline_tput") < 80
    assert read("swa_prefill_attn_roofline_tput") == pytest.approx(
        100 * 4 * 512 * 3_000 * 4 * 48 * 128 / 197e12 / 6.0e-3)
    assert 10 < read("swa_prefill_attn_roofline_tput") < 15
    assert read("kv_window_rows_share_tput") == pytest.approx(55.0)
    # no request past the window: the share reads 100
    stats["window_rows_read"] = stats["full_rows_read"]
    assert read("kv_window_rows_share_tput") == 100.0
    # the accepted readers the cell joins count with THIS block's counts
    stats.update({"moe_experts_touched": 80 * 4 * 6,
                  "moe_assignments_held": 80 * 4 * 9})
    monkeypatch.setattr(
        SS, "subscope_ms_per_launch",
        lambda ctx, names, label: {(("moe_experts",), "decode"): 2.5}.get(
            (names, label)))
    assert read("moe_experts_roofline_tput") == pytest.approx(
        100 * 4 * 6 * 28_311_552 * 2 / 819e9 / 2.5e-3)
    assert read("moe_tokens_per_expert_tput") == 1.5


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
    assert check["reference"] == "benchmarks/reference/swa_moe.py"
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
    cell.config["serve"]["param_scale"] = 5.0
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
    assert s["moe_expert_layer_steps"] == 4 * s["decode_steps"]
    assert 0 < s["moe_experts_touched"] <= s["moe_assignments_held"] \
        <= s["moe_assignments"]
    # requests past the window (16) and past the ring (32 rows) were served
    assert 0 < s["window_rows_read"] < s["full_rows_read"]
    assert s["window_pairs_prefilled"] > 0
    assert (s["decode_inplace_steps"] > 0) == kernel
    assert (s["prefill_inplace_chunks"] > 0) == kernel
    assert max(r["n_prompt"] + r["n_tokens"]
               for r in obs["counters"]["requests"]) > 40


@pytest.mark.parametrize("fault", [
    "window_ignored", "window_off_by_a_page", "ring_not_wrapped",
    "rotary_on_the_full_layer", "attention_gate_left_out",
    "bias_used_as_a_weight", "route_scale_left_out", "matmuls_in_int8"])
def test_a_planted_fault_is_not_correct(fault):
    """A fault of ``swa_moe_faults`` moves served tokens off the
    reference's argmax by more than the tight limits allow, with nothing
    else failing: no request is lost, nothing recompiles."""
    from tests.benchmark import swa_moe_faults
    obs = _drive(TIGHT, swa_moe_faults.FAULTS[fault][0])
    check = obs["check"]
    assert check["ok"] is False and obs["correct"] is False, check
    assert check["retraces_after_warmup"] == 0
    assert check["gap_sigma_mean"] > TIGHT["gap_sigma_mean"]


def test_the_int8_control_lowers_the_program_and_not_the_cells_fields():
    from tests.benchmark import swa_moe_faults
    fields = json.loads(CONFIG.read_text())["fields"]
    plant, names = swa_moe_faults.FAULTS["matmuls_in_int8"]
    assert names == "both"
    with plant():
        assert harness.model_config(fields).matmul_precision == "int8"
    assert harness.model_config(fields).matmul_precision == "bf16"
    assert fields["matmul_precision"] == "bf16"


@pytest.mark.parametrize("fault", [
    "window_ignored", "window_off_by_a_page", "ring_not_wrapped",
    "rotary_on_the_full_layer", "attention_gate_left_out",
    "bias_used_as_a_weight", "route_scale_left_out"])
def test_a_fault_changes_the_decode_program_and_not_the_prefill(fault):
    """Lowered at the rehearsal's size: every planted fault of the file is
    a decode-step fault, so the prefill program's StableHLO is as it
    was."""
    import jax
    import jax.numpy as jnp
    from distributed_training_sandbox_tpu.models import transformer as T
    from distributed_training_sandbox_tpu.serving import engine as E
    from distributed_training_sandbox_tpu.serving.kv_pool import PagedKVPool
    from tests.benchmark import swa_moe_faults
    cfg_file = json.loads(CONFIG.read_text())
    mcfg = harness.model_config({**cfg_file["fields"],
                                 **cfg_file["rehearse"]["fields"]})
    B, P, page, chunk, R_ = 4, 8, 8, 16, 4
    sd = jax.ShapeDtypeStruct
    params = jax.eval_shape(lambda: T.init_params(jax.random.key(0), mcfg))
    bufs = jax.eval_shape(lambda: PagedKVPool(
        mcfg, B * P + 1, page, n_pages_window=B * R_ + 1).bufs)
    i32 = lambda *shape: sd(shape, jnp.int32)  # noqa: E731

    def texts():
        dec = E.make_serve_decode_step(mcfg).trace(
            bufs, params, (i32(B, P), i32(B, R_)), i32(B), i32(B), i32(B),
            sd((B,), jnp.bool_), i32(6)).lower().as_text()
        pre = E.make_serve_prefill_step(mcfg).trace(
            bufs, params, (i32(1, P), i32(1, R_)), i32(1, chunk), i32(),
            i32()).lower().as_text()
        return {"decode": dec, "prefill": pre}

    sound = texts()
    plant, names = swa_moe_faults.FAULTS[fault]
    assert names == "decode"
    with plant():
        faulty = texts()
    assert faulty["decode"] != sound["decode"]
    assert faulty["prefill"] == sound["prefill"]
