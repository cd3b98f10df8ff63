"""The five readers of the program's own collective ledger
(``collective_bytes_per_step``, ``all_gather_exposed_ms``,
``reduce_scatter_exposed_ms``, ``all_gather_busbw_gbps``,
``reduce_scatter_busbw_gbps``): their arithmetic on recorded traces, the
accepted reader as their yardstick, and the faults each would show.  CPU
only: counts and recorded device times, no device metric is made here."""

import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import harness, reduce_trace as R  # noqa: E402
from benchmarks.layer_metrics import _collectives  # noqa: E402
from distributed_training_sandbox_tpu.utils import trace_analysis as TA  # noqa: E402

FIX = ROOT / "tests" / "fixtures"
HAND = str(FIX / "ledger" / "trace_v5e.json")
CHIP_STEP = str(FIX / "trace_v5e_fsdp4_step.json")
READERS = {"collective_bytes_per_step": "GB/step",
           "all_gather_exposed_ms": "ms/step",
           "reduce_scatter_exposed_ms": "ms/step",
           "all_gather_busbw_gbps": "GB/s",
           "reduce_scatter_busbw_gbps": "GB/s"}
PEAKS = harness.load_peaks("TPU v5 lite")


def ctx_of(planes, steps, chips=4):
    """A traced four-chip run whose window holds ``planes``' ops."""
    _collectives._EVENTS.clear()
    _collectives._EVENTS["fixture"] = {
        p: TA._plane_events(ops, {}, chips, None)
        for p, ops in planes.items()}
    return SimpleNamespace(trace=object(), chips=chips, peaks=PEAKS,
                           counters={"steps": steps})


@pytest.fixture(autouse=True)
def the_fixture_is_the_runs_trace(monkeypatch):
    monkeypatch.setattr(R, "find_xplane", lambda trace_dir: "fixture")
    yield
    _collectives._EVENTS.clear()


def read(name, ctx):
    return harness.find_module("layer_metrics", name).read(ctx)


def test_the_readers_are_cell_twos_alone():
    for name, unit in READERS.items():
        mod = harness.find_module("layer_metrics", name)
        assert (mod.LAYER, mod.UNIT, mod.MOVES, mod.RUNNERS) == (
            "strategy / collectives", unit, "train_tokens_per_s", ("train",))
    listed = {row["cell"]: set(row["per_layer"])
              for row in harness.list_cells(ROOT)}
    for cell, metrics in listed.items():
        assert (set(READERS) <= metrics) == (cell == "train-fsdp4-8k"), cell
    entries = harness.load_benchmark(ROOT)["per_layer"]
    assert [e["name"] for e in entries[-5:]] == list(READERS)


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_finds_nothing_without_a_trace_or_on_one_chip(name):
    hand = TA.load_trace(HAND)
    assert read(name, SimpleNamespace(trace=None, chips=4)) is None
    one = ctx_of(hand, 2)
    one.chips = 1
    assert read(name, one) is None
    assert read(name, ctx_of({"/device:TPU:0": []}, 2)) is None


def test_a_program_without_the_reader_gives_nothing(monkeypatch):
    """The parent of the PR that brought ``collective_events``: the import
    fails, every reader returns None, none raises."""
    monkeypatch.delattr(TA, "collective_events")
    for name in READERS:
        assert read(name, SimpleNamespace(trace=object(), chips=4,
                                          peaks=PEAKS,
                                          counters={"steps": 2})) is None


def test_the_arithmetic_on_the_hand_built_trace():
    """Two trips a chip, read as two steps: per step one fused gather
    (2 MiB, in flight 1.06 ms, 50 us exposed), one synchronous (4 KiB, 40 /
    40 us), two reduce-scatters (43 MiB in 500 us, a fused 8.05 MiB in
    100), a permute, a combined all-reduce; the psum once a window."""
    ctx = ctx_of(TA.load_trace(HAND), 2)
    gathers = (2097152 + 4096) * 0.75
    scatters = (45088768 + 8441037) * 0.75
    other = 196611 * 1.0 + 8192 * 1.5
    assert read("collective_bytes_per_step", ctx) == pytest.approx(
        (gathers + scatters + other + 4 * 1.5 / 2) / 1e9)
    assert read("all_gather_exposed_ms", ctx) == pytest.approx(90e-3)
    assert read("reduce_scatter_exposed_ms", ctx) == pytest.approx(600e-3)
    assert read("all_gather_busbw_gbps", ctx) == pytest.approx(
        gathers / 1100e3)                 # bytes per ns are GB/s
    assert read("reduce_scatter_busbw_gbps", ctx) == pytest.approx(
        scatters / 600e3)


def test_the_worst_chip_is_read():
    """Most bytes, most exposed time, least bandwidth: chip 1's
    reduce-scatter takes twice as long, all of it exposed."""
    hand = TA.load_trace(HAND)
    hand["/device:TPU:1"] = [
        op._replace(dur_ns=1000e3, start_ns=op.start_ns - 500e3)
        if op.instruction == "reduce_scatter.196" else op
        for op in hand["/device:TPU:1"]]
    # t 250-350 now lies under fusion.2 (206-300): half hidden, half exposed
    ctx = ctx_of(hand, 2)
    assert read("reduce_scatter_exposed_ms", ctx) == pytest.approx(600e-3)
    assert read("reduce_scatter_busbw_gbps", ctx) == pytest.approx(
        (45088768 + 8441037) * 0.75 / 1100e3)


def test_planted_a_start_and_its_done_both_counted_reads_twice_the_bytes():
    """Both halves of a pair carry a ``bytes_accessed``; a pair is ONE
    record, its message once.  A join that hands back a record a half
    (planted: every pair's record twice) reads the fused gather's and the
    permute's bytes twice, and the gathers' bandwidth over twice its
    own."""
    ctx = ctx_of(TA.load_trace(HAND), 2)
    sound = read("collective_bytes_per_step", ctx)
    sound_bw = read("all_gather_busbw_gbps", ctx)
    planes = _collectives._EVENTS["fixture"]
    pairs = {"async-collective-start": 2097152 * 0.75,
             "collective-permute-start.1": 196611 * 1.0}
    assert {e.instruction for evs in planes.values() for e in evs
            if "start" in e.instruction} == set(pairs)
    _collectives._EVENTS["fixture"] = {
        p: evs + [e for e in evs if e.instruction in pairs]
        for p, evs in planes.items()}
    assert (read("collective_bytes_per_step", ctx) - sound) * 1e9 \
        == pytest.approx(sum(pairs.values()))
    assert read("all_gather_busbw_gbps", ctx) == pytest.approx(
        (2 * 2097152 + 4096) / (2 * 1060e3 + 40e3) * 0.75)
    # the halves' flight doubles with their bytes: the BYTES show the fault
    assert read("all_gather_busbw_gbps", ctx) == pytest.approx(sound_bw,
                                                               rel=0.02)


def test_planted_a_synchronous_collective_booked_as_hidden():
    """A synchronous collective stalls the chip for as long as it runs:
    its exposed time is its duration.  Booked as compute (its opcode read
    as a fusion's), the same trace reads 500 us a step less exposed: what
    ``collective_exposed_pct`` x the step would no longer add up to."""
    hand = TA.load_trace(HAND)
    sound = read("reduce_scatter_exposed_ms", ctx_of(hand, 2))
    hidden = {p: [op._replace(opcode="fusion", ref="fused_computation.7")
                  if op.instruction == "reduce_scatter.196" else op
                  for op in ops] for p, ops in hand.items()}
    assert sound - read("reduce_scatter_exposed_ms", ctx_of(hidden, 2)) \
        == pytest.approx(500e-3)
    evs = _collectives._EVENTS["fixture"]["/device:TPU:0"]
    assert all(e.exposed_ns == e.inflight_ns for e in evs
               if e.instruction in ("all-gather.247", "fusion.371",
                                    "all-reduce.19", "psum.7"))


def test_planted_a_bandwidth_over_the_peak_raises():
    """200 GB/s is everything a chip's ICI links carry; a reading over it
    means the bytes or the time are wrong, and says so."""
    hand = TA.load_trace(HAND)
    fast = {p: [op._replace(dur_ns=op.dur_ns / 100)
                if op.instruction in ("reduce_scatter.196", "fusion.371")
                else op for op in ops] for p, ops in hand.items()}
    with pytest.raises(harness.BenchmarkError, match="over the chip's 200"):
        read("reduce_scatter_busbw_gbps", ctx_of(fast, 2))
    # the other kind's reading is untouched
    assert read("all_gather_busbw_gbps", ctx_of(fast, 2)) < 200


def _raw_of(planes) -> R.RawTrace:
    """``planes`` as ``reduce_trace`` would have loaded them: an op's text
    rebuilt from its parts, cut by ``instruction_name``."""
    def text(op):
        calls = f", calls=%{op.ref}" if op.opcode == "fusion" else ""
        return f"%{op.instruction} = f32[] {op.opcode}(){calls}"
    return R.RawTrace(devices={
        p: {"ops": [(R.instruction_name(text(op)), op.start_ns, op.dur_ns)
                    for op in ops], "modules": [], "async": []}
        for p, ops in planes.items()})


def test_the_accepted_reader_is_the_yardstick():
    """On one trace (a step of cell 2 from the chip), the sum of a chip's
    ``exposed_ns`` is ``reduce_trace``'s ``collective_exposed_ns`` of that
    chip to 1%.  The one place the two part: that reader takes an async
    collective fusion for compute from end to end, this one for a gather
    in flight, so a moment with NOTHING running between the fusion's
    halves is exposed here alone (the hand-built trace plants 50 us of it
    a trip; the chip's step holds 2 us of it in 86 ms)."""
    for trace, gap in ((CHIP_STEP, None), (HAND, 2 * 50e3)):
        planes = TA.load_trace(trace)
        red = R.reduce(_raw_of(planes))
        for chip, (plane, ops) in zip(red.chips, sorted(planes.items())):
            evs = TA._plane_events(ops, {}, 4, None)
            mine = sum(e.exposed_ns for e in evs)
            assert chip.plane == plane and chip.collective_exposed_ns > 0
            if gap is None:
                assert mine == pytest.approx(chip.collective_exposed_ns,
                                             rel=0.01)
            else:
                assert mine - chip.collective_exposed_ns == gap
                assert sum(e.exposed_ns for e in evs if e.instruction
                           == "async-collective-start") == gap


def test_the_chip_step_adds_up_to_the_accepted_share():
    """One step of cell 2 on the chip: the two exposed readers plus the
    other kinds' are ``collective_exposed_pct`` of the step to 1%, the
    bytes are the parameter arithmetic to 2%, and neither bandwidth passes
    the peak: the reduce-scatters run at ~59 GB/s of bus bandwidth, the
    gathers, paced under the matmuls, at ~15."""
    with open(CHIP_STEP) as f:
        doc = json.load(f)
    planes = TA.load_trace(CHIP_STEP)
    ctx = ctx_of(planes, 1)
    red = R.reduce(_raw_of(planes))
    share = red.chips[0].collective_exposed_ns / doc["step_ns"]
    assert 100 * share == pytest.approx(5.72, abs=0.05)
    evs = _collectives._EVENTS["fixture"]["/device:TPU:2"]
    others = sum(e.exposed_ns for e in evs
                 if e.kind not in ("all_gather", "reduce_scatter")) / 1e6
    assert read("all_gather_exposed_ms", ctx) \
        + read("reduce_scatter_exposed_ms", ctx) + others \
        == pytest.approx(share * doc["step_ns"] / 1e6, rel=0.01)
    params = 128256 * 2048 + 2048 + 36 * (
        2 * 2048 * 2048 + 2 * 2048 * 512 + 3 * 2048 * 11008 + 2 * 2048)
    layers = params - 128256 * 2048 - 2048
    arithmetic = (2 * params + layers) * 2 * 0.75 / 1e9
    assert read("collective_bytes_per_step", ctx) == pytest.approx(
        arithmetic, rel=0.02)
    assert read("reduce_scatter_busbw_gbps", ctx) == pytest.approx(
        58.7, abs=1.0)
    assert read("all_gather_busbw_gbps", ctx) == pytest.approx(15.4, abs=0.5)


def test_the_report_lists_kind_scope_and_phase(capsys):
    planes = {p: TA._plane_events(ops, {}, 4, None)
              for p, ops in TA.load_trace(HAND).items()}
    text = _collectives.report(planes, 4, 2)
    rows = [line.split() for line in text.splitlines()[1:]]
    assert [r[1:4] for r in rows][:2] == [
        ["reduce_scatter", "fsdp_layer_gather", "bwd"],
        ["reduce_scatter", "-", "bwd"]]
    assert {r[1] for r in rows} == {"reduce_scatter", "all_gather",
                                    "collective_permute", "all_reduce"}
