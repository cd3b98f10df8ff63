"""The ``loop_dense`` architecture's benchmark files: the configuration
against the catalog's row key by key (nothing cut), the traffic file to the
number, the counts pinned to a hand count, the readers the cell joins and
its four new ones, the planted faults, and the new cell's rehearsal.  CPU
only: counts and control flow, no device metric."""

import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import harness  # noqa: E402
from benchmarks.layer_metrics import _attnscopes as AS  # noqa: E402
from benchmarks.layer_metrics import _loopscopes as LS  # noqa: E402
from benchmarks.layer_metrics import _programs  # noqa: E402

CELL = "serve-loop-reasoning"
NAME = "ouro-2.6b-serve"
TRAFFIC_NAME = "short-reasoning-backlog"
CONFIG = ROOT / f"benchmarks/configs/{NAME}.json"
TRAFFIC = ROOT / f"benchmarks/workloads/{TRAFFIC_NAME}.json"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
#: Ouro-2.6B's published config.json, the numbers (the catalog's row,
#: copied: the test below holds the copy to the row where the catalog is
#: installed)
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2048, "intermediate_size": 5632,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "num_attention_heads": 16, "num_hidden_layers": 48,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-06, "rope_theta": 1000000,
    "total_ut_steps": 4, "early_exit_threshold": 1, "vocab_size": 49152}
SHARED = ("sched_host_ms_per_round_tput", "engine_batch_occupancy_tput",
          "decode_ms_per_step_tput", "prefill_ms_per_chunk_tput",
          "serve_device_idle_pct_tput", "decode_attn_ms_tput",
          "prefill_attn_ms_tput", "decode_inplace_share_tput",
          "prefill_inplace_share_tput", "prefill_head_share_tput",
          "paged_attn_ms_tput", "paged_attn_roofline_tput")
CROSSINGS = ("round_idle_wake_ms_tput", "round_idle_read_ms_tput",
             "round_idle_hostwork_ms_tput",
             "round_idle_launch_latency_ms_tput", "d2h_reads_per_round_tput",
             "h2d_puts_per_round_tput")
#: new reader -> (unit, better, source, layer)
NEW_READERS = {
    "loop_decode_roofline_tput": ("%", "higher", "device_trace", "kernels"),
    "loop_kv_bytes_share_tput": ("%", "lower", "program_counter",
                                 "model step"),
    "loop_gate_ms_tput": ("ms", "lower", "device_trace", "model step"),
    "loop_exit_step_mean_tput": ("passes", "higher", "program_counter",
                                 "model step")}
LAYER = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
PARAMS = 48 * LAYER + 2 * 49_152 * 2048 + 2048 + 2049


def _numbers(d):
    return {k: v for k, v in d.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


@pytest.fixture(scope="module")
def cfg_file():
    return json.loads(CONFIG.read_text())


@pytest.fixture(scope="module")
def counts():
    return harness.find_module("counts", "loop_dense")


# ---------------------------------------------------------- the data files

def test_config_file_is_the_catalog_row_key_by_key(cfg_file):
    f, fields = cfg_file, cfg_file["fields"]
    assert _numbers(f["published"]) == PUBLISHED
    if CATALOG.is_file():
        row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
                   if r["name"] == "Ouro-2.6B")
        assert f["published"] == row["config"]
        assert f["source"] == row["source_url"]
    assert f["reduced"] == []                       # nothing is cut
    for k, v in f["published"].items():
        # the top level of the file is the published config as run; a list
        # (layer_types) is copied whole
        assert f[k] == v, k
        if k in fields:
            assert fields[k] == v, k
    assert f["published"]["layer_types"] == ["full_attention"] * 48
    assert (fields["num_hidden_layers"], fields["total_ut_steps"],
            fields["early_exit_threshold"]) == (48, 4, 1.0)
    assert fields["tie_word_embeddings"] is False
    assert fields["nope_interval"] == 0 and fields["dtype"] == "bfloat16"
    assert f["architecture"] == "loop_dense" and f["runner"] == "serve"
    assert len(f["source"]) <= 200 and len(f["why"]) <= 200
    d = f["deployment"]
    assert (d["chips"], d["chips_sharing_a_layer"], d["layers_held_here"],
            d["passes"]) == (1, 1, 48, 4)
    assert {"sandwich_norms", "final_norm_every_pass", "exit_gate",
            "attention", "mlp", "init", "fused_w_qkv",
            "nope_interval"} == set(f["assumed"])
    assert {"weighted_exit", "fixed_step_exit", "kv_sharing_across_passes",
            "skipping_passes_after_exit"} == set(f["not_run"])
    assert "2,667,974,657" in f["reduced_how"]
    assert set(f["check"]) == {"gap_sigma_mean", "gap_sigma_max", "why"}
    assert set(f["serve"]) >= {"param_scale", "engine", "why"}
    assert f["serve"]["engine"] == {}               # the program's defaults
    r = f["rehearse"]["fields"]
    assert (r["hidden_size"], r["num_hidden_layers"], r["total_ut_steps"],
            r["vocab_size"]) == (64, 3, 3, 512)
    entry = next(c for c in harness.load_benchmark()["configs"]
                 if c["name"] == NAME)
    assert entry == {"name": NAME, "source": f["source"], "reduced": [],
                     "file": f"benchmarks/configs/{NAME}.json",
                     "why": f["why"]}


def test_the_traffic_file_holds_the_issues_table_to_the_number():
    t = json.loads(TRAFFIC.read_text())
    p = t["params"]
    assert t["generator"] == "request_stream"
    assert p["arrival"] == {"process": "backlog", "count": 96}
    assert p["prompt_len"] == {"dist": "lognormal", "median": 160,
                               "sigma": 0.5, "min": 64, "max": 256,
                               "stratified": 8}
    assert p["output_len"] == {"dist": "uniform", "min": 256, "max": 384,
                               "stratified": 8}
    assert p["max_total"] == 640
    assert t["engine"] == {"max_batch": 8, "max_seq_len": 640,
                           "page_size": 16, "prefill_chunk": 256}
    assert (t["drain_s"], t["check"], t["trace"]) == (
        30.0, {"requests": 2, "block": 256}, {"seconds": 6.0})
    assert p["prompt_len"]["max"] + p["output_len"]["max"] <= p["max_total"]
    cells = [w["name"] for w in harness.load_benchmark()["workloads"]
             if w["traffic"] == TRAFFIC_NAME]
    assert CELL in cells


def test_the_cell_reports_the_readers_it_joins_and_its_own():
    bm = harness.load_benchmark()
    cell = harness.load_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        NAME, TRAFFIC_NAME, 1)
    assert len(cell.why) <= 200 and "4x a decode step" in cell.why
    assert [m.name for m in cell.end_to_end] == ["serve_tokens_per_s"]
    names = {m.name for m in cell.per_layer}
    assert names >= {*SHARED, *CROSSINGS, *NEW_READERS}
    assert not any(n.startswith(("lin_", "swa_", "mla_", "moe_", "cca_"))
                   for n in names)
    assert Path(harness.cell_counts(cell).__file__).name == "loop_dense.py"
    entries = {m["name"]: m for m in bm["per_layer"]}
    for name, (unit, better, source, layer) in NEW_READERS.items():
        mod = harness.find_module("layer_metrics", name)
        assert entries[name] == {
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": "serve_tokens_per_s",
            "workloads": [CELL]}
        assert (mod.UNIT, mod.LAYER, mod.MOVES, mod.RUNNERS) == (
            unit, layer, "serve_tokens_per_s", ("serve",))
        for need in getattr(mod, "COUNTS", ()):
            assert hasattr(harness.cell_counts(cell), need), need
    # appended after what was there: the cell thirteenth, its configuration
    # tenth, the four readers in this order after every older entry
    order = [m["name"] for m in bm["per_layer"]]
    first = order.index("loop_decode_roofline_tput")
    assert order[first:first + 4] == list(NEW_READERS)
    assert first > order.index("moe_held_share_tput")
    assert bm["workloads"][12]["name"] == CELL
    assert bm["configs"][9]["name"] == NAME
    assert len((ROOT / "BENCHMARK.json").read_bytes()) < 64 * 1024


# ------------------------------------------------------------- the counts

def test_counts_are_the_equations_arithmetic(cfg_file, counts):
    f = cfg_file["fields"]
    assert counts.layer_weight_count(f) == LAYER == 51_388_416
    assert counts.param_count(f) == PARAMS == 2_667_974_657
    assert round(2 * PARAMS / 1e9, 2) == 5.34
    assert (counts.passes(f), counts.caches(f)) == (4, 192)
    assert counts.kv_bytes_per_token(f) == 192 * 8192 == 1_572_864
    # every layer's weights once a PASS, the head once
    w = counts.decode_step_weight_bytes(f)
    assert w == 2 * (4 * 48 * LAYER + 49_152 * 2048 + 2 * 2048 + 1)
    assert round(w / 1e9, 1) == 19.9
    live = 8 * 400.0
    whole = counts.decode_step_bytes(f, live, rows=8)
    assert whole == w + 8 * 2048 * 2 + live * 1_572_864
    assert round(whole / 1e9, 1) == 25.0        # ~30 ms at 819 GB/s
    assert live * 1_572_864 / whole == pytest.approx(0.20, abs=.01)
    assert counts.model_flops_per_token(f, 0) == 2.0 * (
        4 * 48 * (LAYER - 4 * 2048) + 49_152 * 2048)
    assert counts.model_flops_per_token(f, 640) \
        == counts.model_flops_per_token(f, 0) + 192 * 4 * 16 * 128 * 320
    assert counts.attention_kernel_flops(f, 256, 1) \
        == 192 * 16 * 2 * 256 * 256 * 128
    assert counts.attention_kernel_bytes(f, 256, 1) \
        == 192 * 256 * 128 * 2 * 64
    assert counts.paged_decode_attention_flops(f, live) \
        == 192 * live * 4 * 16 * 128
    assert counts.paged_decode_attention_bytes(f, live, 8) \
        == 192 * (live * 8192 + 8 * 16 * 128 * 6)


def test_counts_are_the_programs_own(cfg_file, counts):
    import math

    import jax
    from distributed_training_sandbox_tpu.models import transformer as T
    from distributed_training_sandbox_tpu.serving import kv_pool
    f = cfg_file["fields"]
    mcfg = harness.model_config(f)
    assert mcfg.loop_dense and mcfg.param_count() == counts.param_count(f)
    shapes = jax.eval_shape(lambda k: T.init_params(k, mcfg),
                            jax.random.key(0))
    assert sum(math.prod(x.shape) for x in jax.tree.leaves(shapes)) \
        == counts.param_count(f)
    tiny = {**f, **cfg_file["rehearse"]["fields"]}
    assert harness.model_config(tiny).param_count() \
        == counts.param_count(tiny)
    assert kv_pool.paged_layers(mcfg) == counts.caches(f)
    assert kv_pool.paged_layers(mcfg) * kv_pool.token_row_bytes(mcfg) \
        == counts.kv_bytes_per_token(f)
    # the memory the cell fills: weights + pages, of 16.9 GB
    t = json.loads(TRAFFIC.read_text())["engine"]
    pages = t["max_batch"] * t["max_seq_len"] // t["page_size"] + 1
    pool = pages * t["page_size"] * counts.kv_bytes_per_token(f)
    assert pages == 321 and round(pool / 1e9, 2) == 8.08
    assert round((2 * counts.param_count(f) + pool) / 1e9, 1) == 13.4


# ------------------------------------------------------------- the scopes

def test_the_engine_opens_the_catalogues_scopes_for_this_block():
    """Lowered at the rehearsal's size with debug info: the block's
    programs carry ``loop_gate`` beneath ``sample`` and ``attn_paged``
    beneath ``attn_core``, and no other block's names."""
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
    bufs = jax.eval_shape(lambda: PagedKVPool(mcfg, B * P + 1, page).bufs)
    assert E.device_counters(mcfg) == ("ut_passes", "exit_step_sum",
                                       "early_exit_rows")
    dec = E.make_serve_decode_step(mcfg).trace(
        bufs, params, i32(B, P), i32(B), i32(B), i32(B), sd((B,), jnp.bool_),
        i32(3)).lower().as_text(debug_info=True)
    pre = E.make_serve_prefill_step(mcfg).trace(
        bufs, params, i32(1, P), i32(1, chunk), i32(),
        i32()).lower().as_text(debug_info=True)
    for text in (dec, pre):
        assert "sample/loop_gate/" in text
        assert "attn_core/attn_paged/" in text
        assert "/moe_" not in text and "/lin_" not in text \
            and "/cca_conv/" not in text
    assert LS.LOOP_SUBSCOPES == profiling.LOOP_SUBSCOPES == ("loop_gate",)
    assert not set(LS.LOOP_SUBSCOPES) & set(
        profiling.SCOPES + profiling.SUBSCOPES + profiling.LINEAR_SUBSCOPES
        + profiling.ATTENTION_SUBSCOPES + profiling.WINDOW_SUBSCOPES
        + profiling.CCA_SUBSCOPES)
    assert LS.innermost("jit(f)/sample/loop_gate/mul") == "loop_gate"
    assert LS.innermost("jit(f)/sample/dot_general") is None


def test_the_loop_scope_table_books_self_time_under_the_name_alone():
    """``_loopscopes.reduce`` on a hand-made trace: ops under ``loop_gate``
    are booked to it by program, an op that nests another counts its own
    time once, ops under other names are left out."""
    from benchmarks.layer_metrics import _scopes as S
    ops = [("fusion.1", 100.0, 40.0, "jit(step)/sample/loop_gate/mul"),
           ("fusion.2", 110.0, 10.0, "jit(step)/sample/loop_gate/add"),
           ("fusion.3", 150.0, 30.0, "jit(step)/sample/dot"),
           ("fusion.4", 300.0, 20.0, "jit(step)/attn_core/attn_paged/x"),
           ("fusion.5", 1100.0, 7.0, "jit(other)/sample/loop_gate/mul")]
    raw = S.ScopedRaw(devices={0: {
        "ops": ops, "modules": [("jit_step(1)", 90.0, 400.0),
                                ("jit_other(2)", 1000.0, 200.0)]}})
    got = LS.reduce(raw, (0.0, 2000.0))
    assert {k: round(v, 6) for k, v in got.items()} == {
        (AS.R.module_group("jit_step(1)"), "loop_gate"): 40.0,
        (AS.R.module_group("jit_other(2)"), "loop_gate"): 7.0}


# -------------------------------------------------------------- the readers

def _ctx(counts, fields, stats, **counters):
    return SimpleNamespace(
        trace=None, fields=fields, counts=counts,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        counters={"stats": stats, "engine": {"max_batch": 8},
                  "program_launches": {}, **counters})


@pytest.mark.parametrize("name", sorted(NEW_READERS))
def test_a_program_without_the_names_gives_the_new_readers_nothing(
        name, cfg_file, counts):
    """The parent's engine has not this block, and an untraced run has no
    table: each reader returns None, never raises; and each is an entry
    away from the cell (a ``workloads`` list of one)."""
    mod = harness.find_module("layer_metrics", name)
    old = {"rounds": 9, "decode_steps": 36, "occupancy_sum": 50,
           "prefill_chunks": 4}
    assert mod.read(_ctx(counts, cfg_file["fields"], old)) is None
    assert mod.read(_ctx(counts, cfg_file["fields"], old, kv_valid_sum=9000,
                         kv_samples=9)) is None
    other = harness.load_cell("serve-cca-moe-longgen")
    assert name not in {m.name for m in other.per_layer}


def test_the_readers_arithmetic(monkeypatch, cfg_file, counts):
    f = cfg_file["fields"]
    steps, live_rows = 40, 40 * 8
    stats = {"rounds": 10, "decode_steps": steps, "occupancy_sum": 80,
             "ut_passes": 4 * live_rows, "exit_step_sum": 4 * live_rows - 16,
             "early_exit_rows": 16, "prefill_chunks": 20,
             "prefill_head_chunks": 20}
    ctx = _ctx(counts, f, stats, kv_valid_sum=10 * 8 * 400, kv_samples=10)
    monkeypatch.setattr(
        LS, "subscope_ms_per_launch",
        lambda ctx, names, label: {(("loop_gate",), "decode"): 0.25}.get(
            (names, label)))
    monkeypatch.setattr(
        AS, "subscope_ms_per_launch",
        lambda ctx, names, label: {(("attn_paged",), "decode"): 8.0}.get(
            (names, label)))
    monkeypatch.setattr(_programs, "device_seconds_per_launch",
                        lambda ctx, label: {"decode": 0.036}.get(label))
    read = lambda name: harness.find_module(  # noqa: E731
        "layer_metrics", name).read(ctx)
    assert read("loop_gate_ms_tput") == 0.25
    whole = counts.decode_step_bytes(f, 3200.0, rows=8.0)
    assert read("loop_decode_roofline_tput") == pytest.approx(
        100 * whole / 819e9 / 0.036)
    assert 80 < read("loop_decode_roofline_tput") < 90
    assert read("loop_kv_bytes_share_tput") == pytest.approx(
        100 * 3200 * 1_572_864 / whole)
    assert 19 < read("loop_kv_bytes_share_tput") < 21
    assert read("loop_exit_step_mean_tput") == pytest.approx(4 - 16 / 320)
    # the accepted readers the cell joins count with THIS block's counts
    assert read("paged_attn_ms_tput") == 8.0
    assert read("paged_attn_roofline_tput") == pytest.approx(
        100 * 192 * (3200 * 8192 + 8 * 16 * 128 * 6) / 819e9 / 8e-3)
    assert read("prefill_head_share_tput") == 100.0


# ------------------------------------------------- the check separates faults

def _drive(check, fault=None, engine=None, seed=11, fields=None):
    """A rehearsal of the cell in this process (the harness's look for a
    chip skipped), held to ``check``; returns the runner's observation.
    (``run.py --rehearse-cpu`` of every cell, this one among them, is
    ``test_bench_rehearse.py``'s.)"""
    import contextlib
    import time
    cell = harness.load_cell(CELL)
    cell.config["check"].update(check)
    # sharper than the cell's own scale: the rehearsal's 64-wide model
    # checks a dozen tokens, and at 1.0 rounding to int8 moves none of them
    cell.config["serve"]["param_scale"] = 1.5
    cell.config["serve"]["engine"].update(engine or {})
    cell.config["fields"].update(fields or {})
    runner = harness.find_module("runners", cell.runner)
    ref = harness.find_module("reference", cell.architecture,
                              needs=runner.REFERENCE_EXPORTS)
    with fault() if fault else contextlib.nullcontext():
        obs = runner.run(cell, ref=ref, seed=seed, seconds=1.0, trace=False,
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
    assert s["admitted"] > 0 and s["decode_steps"] > 0
    # at the published threshold 1.0 every sampled row reads the last pass
    assert s["ut_passes"] > 0 and s["exit_step_sum"] == s["ut_passes"]
    assert s["early_exit_rows"] == 0
    assert (s["decode_inplace_steps"] > 0) == kernel
    assert (s["prefill_inplace_chunks"] > 0) == kernel


def test_rows_leave_early_below_the_published_threshold():
    obs = _drive(TIGHT, fields={"early_exit_threshold": 0.5})
    assert obs["correct"], obs["check"]
    s = obs["counters"]["stats"]
    assert 0 < s["early_exit_rows"] and s["exit_step_sum"] < s["ut_passes"]


@pytest.mark.parametrize("fault", sorted(
    __import__("tests.benchmark.loop_dense_faults",
               fromlist=["FAULTS"]).FAULTS))
def test_a_planted_fault_is_not_correct(fault):
    """A fault of ``loop_dense_faults`` (and the control in int8) moves
    served tokens off the reference's argmax by more than the tight limits
    allow, with nothing else failing: no request is lost, nothing
    recompiles."""
    from tests.benchmark import loop_dense_faults
    obs = _drive(TIGHT, loop_dense_faults.FAULTS[fault][0])
    check = obs["check"]
    assert check["ok"] is False and obs["correct"] is False, check
    assert check["retraces_after_warmup"] == 0
    assert check["gap_sigma_mean"] > TIGHT["gap_sigma_mean"]


def test_a_fault_changes_the_programs_it_says():
    """Lowered at the rehearsal's size: a decode-step fault leaves the
    prefill program's StableHLO as it was; a pass fewer and the control in
    int8 change both."""
    import jax
    import jax.numpy as jnp
    from distributed_training_sandbox_tpu.models import transformer as T
    from distributed_training_sandbox_tpu.serving import engine as E
    from distributed_training_sandbox_tpu.serving.kv_pool import PagedKVPool
    from tests.benchmark import loop_dense_faults
    cfg_file = json.loads(CONFIG.read_text())
    fields = {**cfg_file["fields"], **cfg_file["rehearse"]["fields"]}
    B, P, page, chunk = 4, 8, 8, 16
    sd = jax.ShapeDtypeStruct
    i32 = lambda *shape: sd(shape, jnp.int32)  # noqa: E731

    def texts():
        mcfg = harness.model_config(fields)
        params = jax.eval_shape(
            lambda: T.init_params(jax.random.key(0), mcfg))
        bufs = jax.eval_shape(
            lambda: PagedKVPool(mcfg, B * P + 1, page).bufs)
        dec = E.make_serve_decode_step(mcfg).trace(
            bufs, params, i32(B, P), i32(B), i32(B), i32(B),
            sd((B,), jnp.bool_), i32(3)).lower().as_text()
        pre = E.make_serve_prefill_step(mcfg).trace(
            bufs, params, i32(1, P), i32(1, chunk), i32(),
            i32()).lower().as_text()
        return {"decode": dec, "prefill": pre}

    sound = texts()
    for fault, (plant, programs) in loop_dense_faults.FAULTS.items():
        with plant():
            faulty = texts()
        for program in ("decode", "prefill"):
            assert (faulty[program] != sound[program]) \
                == (programs in (program, "both")), (fault, program)
