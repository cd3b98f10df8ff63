"""Percentile and window arithmetic, the peaks table, the result line, and
the yardstick's FLOP counts (``counts/<architecture>.py``, reached as the
readers reach them) against the program's.  CPU only."""

import json
import math
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import harness  # noqa: E402


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([1, 2, 3, 4], 50, 2.5),
    (list(range(1, 102)), 90, 91.0),
    ([5], 99, 5.0),
    ([10, 20], 90, 19.0),
    ([3, 1, 2], 100, 3.0),
    (iter([4.0, 2.0]), 0, 2.0),
])
def test_percentile(values, q, want):
    assert harness.percentile(values, q) == pytest.approx(want)


def test_percentile_of_nothing_is_nan():
    assert math.isnan(harness.percentile([], 50))


def test_peaks_known_device():
    p = harness.load_peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["ici_bits_per_s"] == 1600e9


@pytest.mark.parametrize("kind", ["TPU v4", "cpu", "", "_source"])
def test_peaks_unknown_device_is_an_error(kind):
    with pytest.raises((harness.BenchmarkError, TypeError)):
        p = harness.load_peaks(kind)
        p["bf16_flops_per_s"]


def test_result_line_has_the_contracts_keys_and_all_digits():
    line = json.loads(harness.result_line(
        correct=True, attempted=3, failed=0,
        metrics={"x_ms": (1.23456789012, "ms")},
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                "memory_peak_bytes": 5}))
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["metrics"]["x_ms"] == {"value": 1.23456789012, "unit": "ms"}


def test_result_line_ends_with_what_was_compared_beside_its_limits():
    line = json.loads(harness.result_line(
        correct=False, attempted=3, failed=0, metrics={"x_ms": (1.0, "ms")},
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                "memory_peak_bytes": 5},
        breakdown={"device_ops": [], "idle_gaps": []},
        compared={"grad_norm_rel.mlp": [0.31, 0.0004],
                  "step_drop": [3.5, 3.1, 4.1]}))
    assert list(line)[-1] == "compared"
    assert line["compared"]["grad_norm_rel.mlp"] == [0.31, 0.0004]
    assert line["compared"]["step_drop"] == [3.5, 3.1, 4.1]


@pytest.mark.parametrize("bad", [math.nan, math.inf, None])
def test_result_line_refuses_a_value_that_is_no_number(bad):
    with pytest.raises(harness.BenchmarkError):
        harness.result_line(correct=True, attempted=1, failed=0,
                            metrics={"x": (bad, "ms")}, device={})


def _ctx(counters, **kw):
    return SimpleNamespace(counters=counters, trace=None, chips=1, **kw)


def _reader(kind, name):
    return harness.find_module(kind, name)


def test_train_tokens_per_s_counts_whole_steps_over_the_window():
    m = _reader("end_to_end", "train_tokens_per_s")
    assert m.read(_ctx({"tokens": 10 * 32768, "elapsed_s": 20.0})) \
        == pytest.approx(16384.0)


def _req(due, first, done, n_tokens, n_prompt=100, in_window=True):
    return {"due_s": due, "t_first_s": first, "t_done_s": done,
            "n_tokens": n_tokens, "n_prompt": n_prompt,
            "in_window": in_window}


def test_ttft_is_timed_from_the_due_time_and_the_unserved_count_as_late():
    m = _reader("end_to_end", "serve_ttft_p90_ms")
    reqs = [_req(float(i), i + 0.1, i + 1.0, 10) for i in range(9)]
    reqs.append(_req(9.0, None, None, 0))          # never got a token
    c = {"backlog": False, "requests": reqs, "end_s": 40.0}
    ttfts = m.ttfts_ms(c)
    assert ttfts[:9] == pytest.approx([100.0] * 9)
    assert ttfts[9] == pytest.approx(31000.0)
    # p90 of nine at 100 ms and one at 31 s sits between them
    assert m.read(_ctx(c)) == pytest.approx(100.0 + 0.1 * 30900.0)


def test_tpot_is_the_median_gap_of_completed_requests():
    m = _reader("end_to_end", "serve_tpot_p50_ms")
    reqs = [_req(0, 1.0, 2.0, 11), _req(0, 1.0, 3.0, 11),
            _req(0, 1.0, 5.0, 11), _req(0, 1.0, None, 3),
            _req(0, 1.0, 1.0, 1)]
    assert m.read(_ctx({"backlog": False, "requests": reqs})) \
        == pytest.approx(200.0)


def test_serve_tokens_per_s_is_processed_tokens_over_the_window_as_it_was():
    m = _reader("end_to_end", "serve_tokens_per_s")
    c = {"backlog": True, "tokens_processed_in_window": 61500,
         "window_s": 10.0, "window_actual_s": 10.25}
    assert m.read(_ctx(c)) == pytest.approx(6000.0)
    assert m.read(_ctx({**c, "backlog": False})) is None


def test_serve_counters_become_per_round_numbers():
    stats = {"rounds": 10, "admit_s": 0.01, "bookkeep_s": 0.03,
             "occupancy_sum": 160, "decode_s": 1.2, "decode_steps": 40,
             "prefill_s": 0.5, "prefill_chunks": 20}
    c = {"stats": stats, "engine": {"max_batch": 32}}
    assert _reader("layer_metrics", "sched_host_ms_per_round").read(
        _ctx(c)) == pytest.approx(4.0)
    assert _reader("layer_metrics", "engine_batch_occupancy").read(
        _ctx(c)) == pytest.approx(50.0)


@pytest.mark.parametrize("name,want_ms", [
    ("decode_ms_per_step", 10e-6), ("decode_ms_per_step_tput", 10e-6),
    ("prefill_ms_per_chunk", 5e-6), ("prefill_ms_per_chunk_tput", 5e-6)])
def test_program_times_are_device_time_per_launch_from_the_trace(name,
                                                                 want_ms):
    """The engine's host-clock ``decode_s`` / ``prefill_s`` are around
    asynchronous dispatch and book a chunk's device time to the burst that
    syncs next: the readers take the module line of the trace instead."""
    from benchmarks import reduce_trace as R
    mods = [("jit__unknown(111)", 0.0, 10.0),
            ("jit__unknown(111)", 20.0, 10.0),
            ("jit__unknown(222)", 40.0, 5.0)]
    red = R.reduce(R.RawTrace(devices={"/device:TPU:0": {
        "ops": [(f"fusion.{i}", s, d) for i, (_, s, d) in enumerate(mods)],
        "modules": mods, "async": []}}, host=[]))
    c = {"program_launches": {"decode": 2, "prefill": 1},
         # host-clock counters that disagree with the trace are not read
         "stats": {"decode_s": 9.0, "decode_steps": 2, "prefill_s": 9.0,
                   "prefill_chunks": 1}}
    m = _reader("layer_metrics", name)
    ctx = SimpleNamespace(counters=c, trace=red, chips=1)
    assert m.read(ctx) == pytest.approx(want_ms)
    assert m.read(_ctx(c)) is None                  # not traced: nothing
    c["program_launches"] = {"decode": 50, "prefill": 70}
    assert m.read(ctx) is None                      # no program fits


def test_generator_lateness_is_a_p99_in_ms_of_the_open_loop_only():
    m = _reader("layer_metrics", "gen_lateness_p99_ms")
    c = {"backlog": False, "lateness_s": [0.001 * i for i in range(101)]}
    assert m.read(_ctx(c)) == pytest.approx(99.0)
    assert m.read(_ctx({**c, "backlog": True})) is None
    assert m.read(_ctx({**c, "lateness_s": []})) is None


def test_first_token_gate_counts_from_the_due_time():
    serve = _reader("runners", "serve")
    req = lambda due, first: SimpleNamespace(  # noqa: E731
        arrival_s=due, t_first=first)
    slo = {"ttft_ms": 1000.0, "min_share": 0.9}
    ok = [req(float(i), i + 0.2) for i in range(9)] + [req(9.0, 10.5)]
    got = serve.first_token_gate(ok, slo)
    assert got["ttft_within_limit_share"] == pytest.approx(0.9)
    assert got["ttft_gate_ok"] is True
    # one more late request, or one that never got a token, fails the run
    for extra in (req(10.0, 11.01), req(10.0, None)):
        bad = serve.first_token_gate(ok + [extra], slo)
        assert bad["ttft_within_limit_share"] == pytest.approx(9 / 11)
        assert bad["ttft_gate_ok"] is False


@pytest.mark.parametrize("loss0,loss1,band,ok", [
    (12.0005, 11.7, [0.2, 0.4], True),
    (12.0040, 11.7, [0.2, 0.4], False),    # the loss is off the reference
    (12.0005, 11.9, [0.2, 0.4], False),    # the update took too little off
    (12.0005, 11.5, [0.2, 0.4], False),    # or too much
    (12.0005, 12.1, None, True),           # no band stated: the loss alone
    (math.nan, 11.7, None, False),
    (12.0005, math.inf, None, False)])
def test_the_real_step_is_held_to_the_reference_and_to_its_drop_band(
        loss0, loss1, band, ok):
    train = _reader("runners", "train")
    got = train.check_step(loss0, loss1, 12.0, {"loss_abs": 0.003}, band)
    assert got["step_ok"] is ok
    if math.isfinite(loss0) and math.isfinite(loss1):
        assert got["step_drop"] == pytest.approx(loss0 - loss1)
        assert got["step_loss_abs_diff"] == pytest.approx(abs(loss0 - 12.0))


def test_a_reader_with_nothing_to_read_returns_nothing():
    c = {"stats": {"rounds": 0, "decode_steps": 0, "prefill_chunks": 0},
         "engine": {"max_batch": 4}, "lateness_s": [], "kv_samples": 0,
         "backlog": False, "program_launches": {},
         "prefetch_wait_s": [], "steps": 1}
    for name in ("sched_host_ms_per_round", "decode_ms_per_step",
                 "prefill_ms_per_chunk", "gen_lateness_p99_ms",
                 "decode_roofline", "step_device_ms", "attn_kernel_ms",
                 "collective_exposed_pct", "host_prefetch_wait_ms"):
        assert _reader("layer_metrics", name).read(_ctx(c)) is None, name


@pytest.fixture(scope="module")
def published():
    return json.loads((ROOT / "benchmarks/configs/smollm3-3b-fsdp4-train.json"
                       ).read_text())["fields"]


@pytest.fixture(scope="module")
def counts():
    return harness.cell_counts(harness.load_cell("train-fsdp4-8k"))


def test_flops_match_the_programs_own_arithmetic(published, counts):
    from distributed_training_sandbox_tpu.models import transformer as T
    from distributed_training_sandbox_tpu.utils.flops import (
        get_model_flops_per_token)
    assert counts.param_count(published) == T.SMOLLM3_3B.param_count()
    assert counts.model_flops_per_token(published, 8192) == pytest.approx(
        get_model_flops_per_token(T.SMOLLM3_3B, 8192))
    assert counts.kv_bytes_per_token(published) == 73728


def test_attention_and_decode_yardsticks_by_hand(published, counts):
    one = {**published, "num_hidden_layers": 1}
    # 6 matmuls x 2·S²·hd per head x 16 heads, halved by the causal mask
    assert counts.attention_kernel_flops(one, 1024, 1) == pytest.approx(
        6 * 2 * 1024 * 1024 * 128 * 16 * 0.5)
    peaks = harness.load_peaks("TPU v5 lite")
    t, bound = harness.roofline_seconds(
        counts.attention_kernel_flops(published, 8192, 1),
        counts.attention_kernel_bytes(published, 8192, 1), peaks)
    assert bound == "compute"
    n = counts.param_count(published)
    assert counts.decode_step_bytes(published, 1000) == pytest.approx(
        2 * n + 1000 * 73728)
    t, bound = harness.roofline_seconds(0.0, 819e9, peaks)
    assert (t, bound) == (pytest.approx(1.0), "memory")


# What ``benchmarks/flops.py`` returned at PR 24 (commit 14c8757) on the three
# configuration files, before its block-dependent functions moved to
# ``counts/dense_gqa.py``: param_count, model_flops_per_token(8192),
# decode_step_bytes(10,000 KV tokens), kv_bytes_per_token,
# attention_kernel_flops / _bytes (8192, 1 window), proj/MLP weights.
PR24_COUNTS = {
    "train-dense-8k": (887654400, 6131023872.0, 1939148800.0, 16384,
                       6597069766656.0, 2013265920.0, 624951296),
    "train-fsdp4-8k": (3075098624, 22073573376.0, 6887477248.0, 73728,
                       29686813949952.0, 9059696640.0, 2812280832),
    "serve-chat": (3075098624, 22073573376.0, 6887477248.0, 73728,
                   29686813949952.0, 9059696640.0, 2812280832),
}


@pytest.mark.parametrize("cell_name", sorted(PR24_COUNTS))
def test_the_counts_reached_through_the_context_are_pr24s(cell_name):
    """The move cannot shift a roofline share: a reader's ``ctx.counts``
    gives, bit for bit, what ``flops.py`` gave."""
    cell = harness.load_cell(cell_name)
    ctx = harness.Context(cell=cell, fields=cell.config["fields"],
                          counters={}, peaks=None,
                          counts=harness.cell_counts(cell))
    f, c = ctx.fields, ctx.counts
    got = (c.param_count(f), c.model_flops_per_token(f, 8192),
           c.decode_step_bytes(f, 10000.0), c.kv_bytes_per_token(f),
           c.attention_kernel_flops(f, 8192, 1),
           c.attention_kernel_bytes(f, 8192, 1), c.proj_mlp_weight_count(f))
    assert got == PR24_COUNTS[cell_name]
    assert Path(c.__file__) == ROOT / "benchmarks/counts/dense_gqa.py"


@pytest.mark.parametrize("name,counters,want", [
    ("train_mfu_pct", {"seq_len": 8192, "tokens": 4 * 32768,
                       "elapsed_s": 7.672},
     100 * 6131023872.0 * 4 * 32768 / (7.672 * 197e12)),
    ("decode_roofline", {"kv_valid_sum": 20000, "kv_samples": 2,
                         "program_launches": {"decode": 2}},
     100 * (1939148800.0 / 819e9) / 0.004),
])
def test_the_counting_readers_read_through_the_context(name, counters, want,
                                                       monkeypatch):
    """``train_mfu_pct`` and ``decode_roofline`` on the 8-layer fields: the
    same arithmetic as at PR 24, with the counts taken from ``ctx.counts``."""
    cell = harness.load_cell("train-dense-8k")
    reader = _reader("layer_metrics", name)
    if name == "decode_roofline":
        from benchmarks.layer_metrics import _programs
        monkeypatch.setattr(_programs, "device_seconds_per_launch",
                            lambda ctx, program: 0.004)
    ctx = SimpleNamespace(counters=counters, fields=cell.config["fields"],
                          chips=1, trace=None, counts=harness.cell_counts(cell),
                          peaks=harness.load_peaks("TPU v5 lite"))
    assert reader.read(ctx) == pytest.approx(want)


def test_a_missing_count_names_the_file(tmp_path):
    (tmp_path / "counts").mkdir()
    (tmp_path / "counts/half_block.py").write_text(
        "def param_count(fields):\n    return 1\n")
    counts = harness.find_module("counts", "half-block", tmp_path,
                                 needs=("param_count",))
    assert counts.param_count({}) == 1
    with pytest.raises(harness.BenchmarkError,
                       match=r"half_block\.py has no 'decode_step_bytes'"):
        harness.find_module("counts", "half-block", tmp_path,
                            needs=("param_count", "decode_step_bytes"))


def test_a_cell_needs_only_the_counts_its_readers_list(tmp_path):
    """``cell_counts`` asks a counts module for what the cell's readers
    list in ``COUNTS`` and no more; a cell none of whose readers counts
    needs no module at all."""
    cell = harness.load_cell("train-dense-8k")
    listed = {n for m in cell.per_layer for n in getattr(m.module, "COUNTS", ())}
    assert listed == {"model_flops_per_token", "proj_mlp_weight_count",
                      "attention_kernel_flops", "attention_kernel_bytes"}
    cell.config["architecture"] = "no-such-block"
    with pytest.raises(harness.BenchmarkError, match=r"no_such_block\.py"):
        harness.cell_counts(cell)
    cell.per_layer = [m for m in cell.per_layer
                      if not hasattr(m.module, "COUNTS")]
    assert harness.cell_counts(cell) is None


def test_a_traffic_files_check_merges_over_the_configurations():
    """A mix at other sizes states the bands it read there; what it does
    not state stays the configuration's."""
    cell = harness.load_cell("train-dense-32k")
    assert cell.check["loss_abs"] == cell.config["check"]["loss_abs"]
    cell.traffic["check"]["step_drop"] = [1.0, 2.0]
    cell.traffic["check"]["grad_norm_rel"] = {"mlp": 0.5}
    assert cell.check["step_drop"] == [1.0, 2.0]
    assert cell.check["grad_norm_rel"] == {
        **cell.config["check"]["grad_norm_rel"], "mlp": 0.5}
    assert cell.config["check"]["grad_norm_rel"]["mlp"] != 0.5   # a copy


@pytest.mark.parametrize("seq,block", [(512, 128), (512, 16), (352, 32)])
def test_the_blocked_reference_has_the_unblocked_loss_and_gradient(seq, block):
    """``reference/dense_gqa.py`` taken ``block`` rows at a time: every block
    on its own context (4 blocks), consecutive blocks in one loop against a
    shared masked context (32 blocks, 8 contexts), and a last context that
    is shorter (11 blocks).  The loss and every gradient leaf are the
    unblocked pass's, in float32 at the rehearsal's widths."""
    import jax
    import jax.numpy as jnp
    from distributed_training_sandbox_tpu.models import transformer as T
    cell = harness.load_cell("train-dense-32k")
    fields = {**cell.config["fields"], **cell.config["rehearse"]["fields"]}
    ref = harness.find_module("reference", cell.architecture)
    params = T.init_params(jax.random.key(1), harness.model_config(fields))
    ids = jax.random.randint(jax.random.key(2), (seq,), 0,
                             fields["vocab_size"])
    labels = jnp.roll(ids, -1)

    def loss_and_grad(b):
        return jax.jit(jax.value_and_grad(
            lambda p: ref.loss(p, ids, labels, fields, block=b)))(params)

    want, want_g = loss_and_grad(None)
    got, got_g = loss_and_grad(block)
    assert float(got) == pytest.approx(float(want), abs=5e-6)
    for a, b in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-7

