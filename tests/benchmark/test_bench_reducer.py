"""The trace reducer on a small recorded trace, against answers computed by
hand.  CPU only: interval arithmetic, no device metric."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import reduce_trace as R  # noqa: E402

US = 1e3    # the fixture's unit, in ns


@pytest.fixture(scope="module")
def red():
    raw = R.RawTrace.from_json(json.loads(
        (ROOT / "benchmarks/fixtures/trace_small.json").read_text()))
    return R.reduce(raw)


def test_window_is_the_marked_span(red):
    assert red.window == (1000 * US, 2000 * US)
    assert red.window_s == pytest.approx(1e-3)


def test_busy_union_and_idle_share(red):
    chip0, chip1 = red.chips
    # chip 0: [1000,1020) + [1050,1400) + [1450,1850); the while is a
    # container and the event before the window is cut to it
    assert chip0.busy_ns == pytest.approx(770 * US)
    assert chip0.idle_share == pytest.approx(0.23)
    assert chip1.busy_ns == pytest.approx(900 * US)
    assert red.busy_s == pytest.approx(835e-6)
    assert red.worst("idle_share") == pytest.approx(0.23)


def test_op_groups_strip_the_numeric_suffix(red):
    g = red.chips[0].op_groups
    assert g == pytest.approx({
        "fusion": 460 * US, "all-gather-start": 10 * US,
        "all-gather-done": 50 * US, "splash_mha_fwd": 200 * US,
        "all-reduce": 50 * US})
    assert "while" not in g
    assert red.group_ns(r"splash") == pytest.approx(100 * US)  # mean of chips


def test_modules(red):
    launches, ns = red.chips[0].modules["jit_step"]
    assert launches == 2 and ns == pytest.approx(850 * US)
    assert red.chips[1].modules == {"jit_step": (1, pytest.approx(900 * US))}


def test_collective_in_flight_and_exposed(red):
    chip0, chip1 = red.chips
    # in flight: the async pair [1250,1500) and the all-reduce [1700,1750)
    assert chip0.collective_inflight_ns == pytest.approx(300 * US)
    # exposed: [1250,1260) + [1400,1500) + [1700,1750); fusion.2 hides
    # [1260,1400)
    assert chip0.collective_exposed_ns == pytest.approx(160 * US)
    # chip 1: an async-line all-gather [1100,1300) wholly under fusion.1;
    # the copy-start on that line is no collective
    assert chip1.collective_inflight_ns == pytest.approx(200 * US)
    assert chip1.collective_exposed_ns == 0


def test_gaps_go_to_the_host_span_open_at_the_time(red):
    gaps = {(s / US, d / US): span for s, d, span in red.chips[0].gaps}
    assert gaps == {(1850, 150): "bench/pump_drain",
                    (1400, 50): "bench/pump_drain",
                    (1020, 30): "bench/prefetch_wait"}
    assert [(s / US, d / US, n) for s, d, n in red.chips[1].gaps] == [
        (1900, 100, "bench/pump_drain")]


def test_breakdown_is_the_contracts_shape(red):
    b = red.breakdown()
    # fusion.9 [1000,1020) ran in the first launch, the rest in the second
    assert b["device_ops"][0] == ["jit_step:fusion", pytest.approx(460e-6)]
    assert b["device_ops"][1] == ["jit_step:splash_mha_fwd",
                                  pytest.approx(200e-6)]
    assert b["idle_gaps"] == [["bench/pump_drain", pytest.approx(200e-6)],
                              ["bench/prefetch_wait", pytest.approx(30e-6)]]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


@pytest.mark.parametrize("a,b,want", [
    ([(0, 10)], [(2, 4), (6, 12)], [(0, 2), (4, 6)]),
    ([(0, 10), (20, 30)], [], [(0, 10), (20, 30)]),
    ([(0, 10)], [(0, 10)], []),
    ([(5, 6)], [(0, 5), (6, 9)], [(5, 6)]),
])
def test_subtract(a, b, want):
    assert R.subtract(a, b) == want


def test_union_merges_touching_and_nested():
    assert R.union([(5, 7), (0, 3), (1, 2), (3, 4), (9, 9)]) == [
        (0, 4), (5, 7)]


@pytest.mark.parametrize("name,group", [
    ("fusion.123", "fusion"), ("all-gather-start.4.1", "all-gather-start"),
    ("%copy.2", "copy"), ("scope/inner/dot_general.7", "dot_general"),
    ("splash_mha_fwd", "splash_mha_fwd")])
def test_op_group(name, group):
    assert R.op_group(name) == group


@pytest.mark.parametrize("name,yes", [
    ("all-gather-start.3", True), ("all_gather.42", True), ("psum.7", True),
    ("reduce-scatter.1", True), ("collective-permute-done.2", True),
    ("fusion.3", False), ("gather.3", False), ("all-gather-fusion", False)])
def test_is_collective(name, yes):
    assert R.is_collective(name) is yes


def test_a_trace_without_device_events_is_an_error():
    with pytest.raises(ValueError):
        R.reduce(R.RawTrace(devices={}, host=[("bench/window", 0.0, 1.0)]))


def test_rawtrace_round_trips_through_json(red):
    raw = R.RawTrace.from_json(json.loads(
        (ROOT / "benchmarks/fixtures/trace_small.json").read_text()))
    again = R.RawTrace.from_json(json.loads(json.dumps(raw.to_json())))
    assert again == raw


@pytest.mark.parametrize("text,name", [
    ("select_add_fusion.6 = bf16[4,8192,2048]{2,1,0:T(8,128)(2,1)} fusion("
     "bf16[4,8192,2048]{2,1,0} %get-tuple-element.1997), kind=kOutput, "
     "calls=%fused_computation.62.clone", "select_add_fusion.6"),
    ("splash_mha_dkv_no_residuals.11 = (f32[4,1024,128]{2,1,0}, "
     "/*index=5*/bf16[4,4,8192,128]) custom-call(s8[1,16,8] %x), "
     "custom_call_target=\"tpu_custom_call\"",
     "splash_mha_dkv_no_residuals.11"),
    ("%fusion.429 = bf16[528,2048]{1,0} fusion(%fusion.428), kind=kCustom, "
     "calls=%all-reduce-scatter.2.clone.clone", "reduce-scatter.429"),
    ("%all-gather.247 = bf16[1,2048]{1,0} all-gather(%x.41), channel_id=2",
     "all-gather.247"),
    ("%cp.2 = (bf16[48,2048]{1,0}, u32[]) collective-permute-start("
     "%slice.48), channel_id=36", "collective-permute-start.2"),
    ("psum.7 = f32[] all-reduce(f32[] %x), replica_groups={}", "psum.7"),
    ("while.97 = (s32[], bf16[1,8192,2048]) while(%tuple.335), "
     "condition=%c, body=%b", "while.97"),
    ("fusion.12", "fusion.12")])
def test_instruction_name_cuts_a_tpu_events_text(text, name):
    """On a v5e an op event's name is the instruction's whole text."""
    assert R.instruction_name(text) == name


def _two_unnamed_programs():
    ops = [("fusion.1", 0.0, 10.0), ("fusion.2", 20.0, 10.0),
           ("fusion.3", 40.0, 5.0), ("copy.1", 50.0, 1.0)]
    mods = [("jit__unknown(111)", 0.0, 10.0), ("jit__unknown(111)", 20.0, 10.0),
            ("jit__unknown(222)", 40.0, 5.0), ("jit_named(9)", 50.0, 1.0)]
    return R.reduce(R.RawTrace(devices={"/device:TPU:0": {
        "ops": ops, "modules": mods, "async": []}}, host=[]))


def test_unnamed_programs_keep_their_fingerprint_and_get_a_label_by_count():
    """Both engine programs are ``jit__unknown`` in a v5e trace."""
    red = _two_unnamed_programs()
    assert set(red.chips[0].modules) == {
        "jit__unknown(111)", "jit__unknown(222)", "jit_named"}
    al = R.alias_modules(red, {"decode": 2, "prefill": 1})
    assert al == {"jit__unknown(111)": "decode",
                  "jit__unknown(222)": "prefill"}
    ops = dict(map(tuple, red.breakdown(aliases=al)["device_ops"]))
    assert ops == pytest.approx({"decode:fusion": 20e-9,
                                 "prefill:fusion": 5e-9,
                                 "jit_named:copy": 1e-9})
    # a count that fits no program names none; a named program is never
    # renamed
    assert R.alias_modules(red, {"decode": 50}) == {}
    assert R.alias_modules(red, {}) == {}


def test_a_step_recorded_on_a_v5e_reduces_as_the_run_reported():
    """One real traced step of ``train-dense-8k`` (chip 0, PR 22): the
    structure the reducer relies on is there, and the arithmetic gives what
    that run's result line gave per step."""
    raw = R.RawTrace.from_json(json.loads(
        (ROOT / "benchmarks/fixtures/trace_v5e_train_step.json").read_text()))
    ops = raw.devices["/device:TPU:0"]["ops"]
    assert sum(n.startswith("while") for n, _, _ in ops) == 4   # containers
    assert sum(n.startswith("splash_mha_fwd") for n, _, _ in ops) == 16
    red = R.reduce(raw)
    chip = red.chips[0]
    assert chip.modules == {"jit_step": (1, pytest.approx(1917226010.0))}
    # the whiles span their bodies; counted as containers, the chip is busy
    # for the launch and idle only while the host dispatches it
    assert red.busy_s == pytest.approx(1.9172, abs=1e-4)
    assert chip.idle_share < 1e-3
    assert chip.gaps[0][2] == "bench/dispatch"
    kernels = {g: ns for g, ns in chip.op_groups.items() if "splash" in g}
    assert set(kernels) == {"splash_mha_fwd_residuals",
                            "splash_mha_dq_no_residuals",
                            "splash_mha_dkv_no_residuals"}
    assert sum(kernels.values()) / 1e6 == pytest.approx(375.7, abs=0.1)
    assert "while" not in chip.op_groups and chip.collective_inflight_ns == 0
