"""``attn_fwd_ms``: the device time of the splash FORWARD calls a step,
on a synthetic trace and on the recorded v5e step, with and without the
forward's remat re-run.  CPU only: interval arithmetic, no device metric."""

import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import harness  # noqa: E402
from benchmarks import reduce_trace as R  # noqa: E402

LAYERS, STEPS = 3, 2
FWD, DKV, DQ = 40.0, 36.0, 27.0      # ns a call, in the synthetic trace
TRAIN_CELLS = ["train-dense-8k", "train-fsdp4-8k", "train-dense-32k"]


@pytest.fixture(scope="module")
def reader():
    return harness.find_module("layer_metrics", "attn_fwd_ms")


def _ctx(raw, steps):
    return SimpleNamespace(trace=R.reduce(raw), counters={"steps": steps})


def _synthetic(rerun: bool, chips: int = 1) -> R.RawTrace:
    """``STEPS`` launches of a step whose forward scan runs the forward
    kernel once a layer and whose backward scan runs dkv and dq and, with
    ``rerun``, the forward again; matmuls between them."""
    devices = {}
    for chip in range(chips):
        ops, mods, t, n = [], [], 0.0, 0

        def put(name, dur):
            nonlocal t, n
            n += 1
            ops.append((f"{name}.{n}", t, dur))
            t += dur

        for _ in range(STEPS):
            t0 = t
            for _ in range(LAYERS):
                put("fusion", 100.0)
                put("splash_mha_fwd_residuals", FWD)
            for _ in range(LAYERS):
                if rerun:
                    put("splash_mha_fwd_residuals", FWD)
                put("splash_mha_dkv_no_residuals", DKV)
                put("splash_mha_dq_no_residuals", DQ)
                put("fusion", 200.0)
            mods.append(("jit_step(7)", t0, t - t0))
            t += 5.0
        devices[f"/device:TPU:{chip}"] = {"ops": ops, "modules": mods,
                                          "async": []}
    return R.RawTrace(devices=devices, host=[])


@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("rerun,calls", [(True, 2), (False, 1)])
def test_attn_fwd_ms_is_the_forward_calls_of_a_step(reader, rerun, calls,
                                                    chips):
    """Per step and chip: ``calls`` forward calls a layer; it halves when
    the re-run goes, and the other kernels' time does not enter it."""
    assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.RUNNERS) == (
        "kernels", "ms/step", "train_tokens_per_s", ("train",))
    ctx = _ctx(_synthetic(rerun, chips), STEPS)
    assert reader.read(ctx) == pytest.approx(calls * LAYERS * FWD / 1e6)
    every = harness.find_module("layer_metrics", "attn_kernel_ms")
    assert every.read(ctx) - reader.read(ctx) == pytest.approx(
        LAYERS * (DKV + DQ) / 1e6)


def test_attn_fwd_ms_finds_nothing_without_a_trace_or_a_forward(reader):
    assert reader.read(SimpleNamespace(trace=None, counters={})) is None
    bare = R.RawTrace(devices={"/device:TPU:0": {
        "ops": [("fusion.1", 0.0, 10.0), ("custom-call.2", 10.0, 5.0)],
        "modules": [("jit_step(7)", 0.0, 15.0)], "async": []}}, host=[])
    assert reader.read(_ctx(bare, 1)) is None


def test_on_the_recorded_v5e_step_with_and_without_the_rerun(reader):
    """One real traced step of ``train-dense-8k`` (PR 22, the re-run still
    there): 16 forward calls = 146.46 ms of the kernels' 375.7; with the
    8 calls of the backward scan taken out, what is left is half."""
    obj = json.loads((ROOT / "benchmarks/fixtures/"
                      "trace_v5e_train_step.json").read_text())
    with_rerun = reader.read(_ctx(R.RawTrace.from_json(obj), 1))
    assert with_rerun == pytest.approx(146.46, abs=0.01)
    ops = obj["devices"]["/device:TPU:0"]["ops"]
    fwd = sorted((o for o in ops if o[0].startswith("splash_mha_fwd")),
                 key=lambda o: o[1])
    assert len(fwd) == 16
    obj["devices"]["/device:TPU:0"]["ops"] = [
        o for o in ops if o not in fwd[8:]]
    once = reader.read(_ctx(R.RawTrace.from_json(obj), 1))
    assert once == pytest.approx(with_rerun / 2, rel=0.01)


def test_attn_fwd_ms_is_listed_for_the_training_cells():
    """The three cells it was accepted in, in their order; a training cell
    that a later PR appends joins both lists."""
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    (entry,) = [m for m in bm["per_layer"] if m["name"] == "attn_fwd_ms"]
    listed = entry.pop("workloads")
    assert entry == {
        "name": "attn_fwd_ms", "unit": "ms/step", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "train_tokens_per_s"}
    (rate,) = [m for m in bm["end_to_end"]
               if m["name"] == "train_tokens_per_s"]
    for cells in (listed, rate["workloads"]):
        assert [c for c in cells if c in TRAIN_CELLS] == TRAIN_CELLS
    # every cell the kernel's time is listed in reports the rate it moves
    assert set(listed) <= set(rate["workloads"])
