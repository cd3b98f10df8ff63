"""Percentile and window arithmetic, the peaks table, the result line, and
the yardstick's FLOP counts against the program's.  CPU only."""

import json
import math
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import flops, harness  # noqa: E402


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


def test_flops_match_the_programs_own_arithmetic(published):
    from distributed_training_sandbox_tpu.models import transformer as T
    from distributed_training_sandbox_tpu.utils.flops import (
        get_model_flops_per_token)
    assert flops.param_count(published) == T.SMOLLM3_3B.param_count()
    assert flops.model_flops_per_token(published, 8192) == pytest.approx(
        get_model_flops_per_token(T.SMOLLM3_3B, 8192))
    assert flops.kv_bytes_per_token(published) == 73728


def test_attention_and_decode_yardsticks_by_hand(published):
    one = {**published, "num_hidden_layers": 1}
    # 6 matmuls x 2·S²·hd per head x 16 heads, halved by the causal mask
    assert flops.attention_kernel_flops(one, 1024, 1) == pytest.approx(
        6 * 2 * 1024 * 1024 * 128 * 16 * 0.5)
    peaks = harness.load_peaks("TPU v5 lite")
    t, bound = flops.roofline_seconds(
        flops.attention_kernel_flops(published, 8192, 1),
        flops.attention_kernel_bytes(published, 8192, 1), peaks)
    assert bound == "compute"
    n = flops.param_count(published)
    assert flops.decode_step_bytes(published, 1000) == pytest.approx(
        2 * n + 1000 * 73728)
    t, bound = flops.roofline_seconds(0.0, 819e9, peaks)
    assert (t, bound) == (pytest.approx(1.0), "memory")
