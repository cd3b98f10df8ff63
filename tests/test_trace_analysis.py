"""The program's one reader of a profiler trace (utils.trace_analysis):
collective events with their kind, scope, bytes, in-flight and exposed
time from the ``.xplane.pb``, and the comm-vs-compute split that stands on
them — the twin of the reference's in-optimizer communication timers
(``zero/zero2.py:219-228``).

The deterministic half reads recorded traces: a hand-built one in the form
a v5e's ``XLA Ops`` line has (``tests/fixtures/ledger/trace_v5e.json``,
whole tens of microseconds) and ONE step of the four-chip FSDP cell from the chip
(``tests/fixtures/trace_v5e_fsdp4_step.json``).  The live half traces the
CPU simulator, whose ``.xplane.pb`` names a thunk after its instruction."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from distributed_training_sandbox_tpu.ops import collectives as C
from distributed_training_sandbox_tpu.ops.busbench import bus_factor
from distributed_training_sandbox_tpu.utils import trace_analysis as TA
from distributed_training_sandbox_tpu.utils.trace_analysis import Op

FIX = Path(__file__).parent / "fixtures"
HAND = str(FIX / "ledger" / "trace_v5e.json")
HAND_HLO = (FIX / "ledger" / "step_v5e.hlo.txt").read_text()
CHIP_STEP = str(FIX / "trace_v5e_fsdp4_step.json")


# ------------------------------------------------------- an event's name

def test_parse_event_name_forms():
    """A TPU event's name is the instruction's text; the CPU simulator's
    is the instruction's name, possibly behind ``%`` or a scope."""
    text = ("%fusion.365 = bf16[2912,2048]{1,0:T(8,128)(2,1)S(1)} fusion("
            "bf16[11008,2048]{1,0:T(8,128)(2,1)} %get-tuple-element.2593), "
            "kind=kCustom, calls=%all-reduce-scatter.clone.clone")
    assert TA.parse_event_name(text) == (
        "fusion.365", "fusion", "all-reduce-scatter.clone.clone")
    # a tuple shape holds parentheses of its own in its layouts
    start = ("%collective-permute-start.2 = (bf16[48,2048]{1,0:T(8,128)(2,1)"
             "S(1)}, bf16[48,2048]{1,0:T(8,128)(2,1)S(1)}, u32[]{:S(2)}) "
             "collective-permute-start(bf16[48,2048]{1,0} %slice.39), "
             "channel_id=36")
    assert TA.parse_event_name(start) == (
        "collective-permute-start.2", "collective-permute-start", "")
    done = ("%collective-permute-done.2 = bf16[48,2048]{1,0:T(8,128)(2,1)} "
            "collective-permute-done((bf16[48,2048]{1,0}, u32[]{:S(2)}) "
            "%collective-permute-start.2)")
    assert TA.parse_event_name(done) == (
        "collective-permute-done.2", "collective-permute-done",
        "collective-permute-start.2")
    for name in ("all-reduce.1", "%all-reduce.1", "while/body/all-reduce.1"):
        assert TA.parse_event_name(name) == ("all-reduce.1", "", "")
        assert TA.normalize_event_name(name) == "all-reduce.1"


def _op(instruction, opcode="", ref="", path=""):
    return Op(instruction, opcode, ref, 0.0, 1.0, path, None)


@pytest.mark.parametrize("op, want", [
    # XLA:TPU, by opcode: the instruction may be named after the primitive
    (_op("all-gather.247", "all-gather"), ("all_gather", "")),
    (_op("reduce_scatter.196", "reduce-scatter"), ("reduce_scatter", "")),
    (_op("psum.7", "all-reduce"), ("all_reduce", "")),
    (_op("collective-permute-start.2", "collective-permute-start"),
     ("collective_permute", "start")),
    (_op("all-gather-done.4", "all-gather-done", "all-gather-start.4"),
     ("all_gather", "done")),
    # a reduce-scatter run as ONE fusion: an all-reduce and its slice
    (_op("fusion.365", "fusion", "all-reduce-scatter.clone.clone"),
     ("reduce_scatter", "")),
    (_op("fusion.19", "fusion", "all-reduce-scatter.3"),
     ("reduce_scatter", "")),
    # an async collective fusion's halves; the fusions between are compute
    (_op("async-collective-start.4", "fusion", "fused_computation.300"),
     ("all_gather", "start")),
    (_op("async-collective-done.4", "fusion", "fused_computation.302"),
     ("all_gather", "done")),
    (_op("fusion.378", "fusion", "async_collective_fusion.378"),
     (None, "")),
    # the CPU simulator: names alone, both spellings
    (_op("psum.7"), ("all_reduce", "")),
    (_op("all_gather.52"), ("all_gather", "")),
    (_op("all-reduce-start.3"), ("all_reduce", "start")),
    (_op("ppermute.9"), ("collective_permute", "")),
    (_op("all_to_all.3"), ("all_to_all", "")),
    # compute whose names hold a collective's words
    (_op("gather.3"), (None, "")),
    (_op("reduce.6"), (None, "")),
    (_op("scatter.5"), (None, "")),
    (_op("reduce-window.1", "reduce-window"), (None, "")),
    (_op("dynamic-update-slice_fusion.2", "fusion", "fused_computation.9"),
     (None, "")),
])
def test_classify(op, want):
    assert TA.classify(op) == want


def test_innermost_scope_and_phase_words():
    assert TA.innermost_scope(
        "jit(step)/shard_map/forward_backward/transpose(jvp())/while/body/"
        "closed_call/checkpoint/fsdp_layer_gather/reduce_scatter") \
        == "fsdp_layer_gather"
    assert TA.innermost_scope("jit(step)/transpose(jvp(mlp))/dot_general") \
        == "mlp"
    assert TA.innermost_scope("jit(step)/shard_map/while") is None
    assert TA.innermost_scope("") is None


# ------------------------------------------- the metadata ProfileData hides

def _varint(n: int) -> bytes:
    out = b""
    while True:
        n, low = n >> 7, n & 0x7F
        out += bytes([low | (0x80 if n else 0)])
        if not n:
            return out


def _msg(*fields) -> bytes:
    """``(number, value)``: an int is a varint, bytes length-delimited."""
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
        else:
            out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return out


def test_event_metadata_decodes_a_string_and_an_integer_stat():
    """``tf_op`` as a string and as a reference to an interned one, and
    ``bytes_accessed`` as an unsigned integer, from ``xplane.proto``'s wire
    format; host planes and stats nobody asked for are skipped."""
    stat = lambda i, name: (5, _msg((1, i), (2, _msg((1, i), (2, name)))))  # noqa: E731
    event = lambda i, name, *stats: (4, _msg((1, i), (2, _msg(  # noqa: E731
        (1, i), (2, name), *((5, _msg(*s)) for s in stats)))))
    path = b"jit(step)/mlp/dot_general:"
    plane = _msg(
        (2, b"/device:TPU:0"),
        stat(1, b"tf_op"), stat(2, b"bytes_accessed"), stat(3, b"flops"),
        stat(4, b"jit(step)/loss_mean/psum"),
        event(1, b"%fusion.1 = f32[8] fusion()", ((1, 1), (5, path)),
              ((1, 2), (3, 656670720)), ((1, 3), (3, 99))),
        event(2, b"%psum.7 = f32[] all-reduce()", ((1, 1), (7, 4)),
              ((1, 2), (4, 8))),
        event(3, b"%copy.1 = f32[8] copy()", ((1, 3), (3, 1))))
    host = _msg((2, b"/host:CPU"), stat(1, b"tf_op"),
                event(1, b"x", ((1, 1), (5, b"y"))))
    meta = TA.event_metadata(_msg((1, plane), (1, host)))
    assert meta == {"/device:TPU:0": {
        "%fusion.1 = f32[8] fusion()": {"tf_op": path.decode(),
                                        "bytes_accessed": 656670720},
        "%psum.7 = f32[] all-reduce()": {"tf_op": "jit(step)/loss_mean/psum",
                                         "bytes_accessed": 8}}}


# --------------------------------------------------- the records, by hand

def test_hand_built_trace_gives_one_record_a_collective():
    """Pairs are one event under the start's name, a fused reduce-scatter
    one under the fusion's, in order of start; every field as built."""
    planes = TA.collective_events(HAND, group=4)
    assert sorted(planes) == ["/device:TPU:0", "/device:TPU:1"]
    evs = planes["/device:TPU:0"]
    assert [e.instruction for e in evs] == 2 * [
        "async-collective-start", "all-gather.247", "reduce_scatter.196",
        "fusion.371", "collective-permute-start.1", "all-reduce.19"] \
        + ["psum.7"]
    got = [(e.kind, e.start_ns, e.inflight_ns, e.exposed_ns, e.bytes,
            e.bytes_source, e.scope, e.phase) for e in evs[:6]]
    assert got == [
        ("all_gather", 100e4, 106e4, 5e4, 2097152, "trace", None, "fwd"),
        ("all_gather", 110e4, 4e4, 4e4, 4096, "trace",
         "fsdp_layer_gather", "fwd"),
        ("reduce_scatter", 300e4, 50e4, 50e4, 45088768, "trace",
         "fsdp_layer_gather", "bwd"),
        ("reduce_scatter", 350e4, 10e4, 10e4, 8441037, "trace", None, "bwd"),
        ("collective_permute", 360e4, 26e4, 5e4, 196611, "trace", None,
         "bwd"),
        ("all_reduce", 380e4, 2e4, 2e4, 8192, "trace", None, "bwd")]
    assert evs[-1].scope == "loss_mean" and evs[-1].bytes == 4


def test_the_compiled_text_names_what_a_fusion_hides():
    """A fusion's own path is cut where its ops' paths part; with the
    compiled text the async collective fusion gets its collective's scope,
    and an event without ``bytes_accessed`` the site's payload."""
    evs = TA.collective_events(HAND, hlo_text=HAND_HLO, group=4)[
        "/device:TPU:0"]
    assert (evs[0].instruction, evs[0].kind, evs[0].scope, evs[0].phase) \
        == ("async-collective-start", "all_gather", "fsdp_layer_gather",
            "fwd")
    bare = {p: [op._replace(nbytes=None) for op in ops]
            for p, ops in TA.load_trace(HAND).items()}
    from distributed_training_sandbox_tpu.telemetry.ledger import (
        collective_sites)
    sites = {s.name: s for s in collective_sites(HAND_HLO)}
    got = TA._plane_events(bare["/device:TPU:0"], sites, 4, None)
    assert {(e.instruction, e.bytes, e.bytes_source) for e in got} == {
        ("async-collective-start", 2097152, "hlo"),
        ("all-gather.247", 4096, "hlo"),
        ("reduce_scatter.196", 45088768, "hlo"),
        ("fusion.371", 8650752, "hlo"),      # the padded all-reduce
        ("collective-permute-start.1", 196608, "hlo"),
        ("all-reduce.19", 8192, "hlo"), ("psum.7", 4, "hlo")}
    none = TA._plane_events(bare["/device:TPU:0"], {}, 4, None)
    assert {(e.bytes, e.bytes_source) for e in none} == {(None, None)}


def test_exposed_time_is_booked_once_to_the_collective_that_started_last():
    """Inside the permute's flight (3.60-3.86 ms) the all-reduce executes:
    its 20 us are the all-reduce's, the permute keeps its own start and the
    idle tail; the sum is the union's exposed time, never more."""
    evs = TA.collective_events(HAND, group=4)["/device:TPU:0"]
    by = {}
    for e in evs:
        by.setdefault(e.instruction, []).append(e.exposed_ns)
    assert by["collective-permute-start.1"] == [5e4, 5e4]
    assert by["all-reduce.19"] == [2e4, 2e4]
    # the gather's halves are fusions (compute while they run): only the
    # idle gap inside its flight is exposed, less what all-gather.247 took
    assert by["async-collective-start"] == [5e4, 5e4]
    ops = TA.load_trace(HAND)["/device:TPU:0"]
    flights = TA._union((e.start_ns, e.start_ns + e.inflight_ns)
                        for e in evs)
    compute = TA._union(
        (o.start_ns, o.start_ns + o.dur_ns) for o in ops
        if o.opcode == "fusion" and not o.ref.startswith("all-reduce-sc"))
    assert sum(e.exposed_ns for e in evs) \
        == TA._total(TA._subtract(flights, compute)) == 2 * 76e4 + 2e4


def test_a_window_cuts_the_events_to_it():
    evs = TA.collective_events(HAND, group=4, window=(320e4, 510e4))[
        "/device:TPU:0"]
    assert [(e.instruction, e.start_ns, e.inflight_ns) for e in evs][:2] \
        == [("reduce_scatter.196", 320e4, 30e4), ("fusion.371", 350e4, 10e4)]
    # the second trip's gather started at 500: cut at the window's end
    assert (evs[-1].instruction, evs[-1].inflight_ns) \
        == ("async-collective-start", 10e4)
    assert "psum.7" not in {e.instruction for e in evs}


def test_event_stats_keep_the_ledgers_shape():
    stats = TA.collective_event_stats(HAND)
    assert stats["async-collective-start"] == {"count": 4, "total_us": 4240.0}
    assert stats["reduce_scatter.196"] == {"count": 4, "total_us": 2000.0}
    assert stats["psum.7"] == {"count": 2, "total_us": 40.0}
    assert not any(n.startswith(("fusion.1", "fusion.378", "while",
                                 "async-collective-done",
                                 "collective-permute-done"))
                   for n in stats)


@pytest.mark.parametrize("kind, asynchronous, accessed, message", [
    # as read on the chip (PR 52): operands plus results
    ("all_gather", False, 656670720, 128256 * 2048 * 2),
    ("reduce_scatter", False, 56360960, 2048 * 11008 * 2),
    ("all_reduce", False, 16384, 2 * 2048 * 2),
    # an async start's result tuple aliases its operand
    ("all_gather", True, 67633216, 2048 * 11008 * 2),
    ("collective_permute", True, 589824, 48 * 2048 * 2),
])
def test_payload_bytes_is_the_message(kind, asynchronous, accessed, message):
    assert TA.payload_bytes(kind, accessed, 4, asynchronous) \
        == pytest.approx(message, rel=1e-6)


# --------------------------------------------------------------- the split

def test_split_of_the_hand_built_trace():
    sp = TA.comm_split(TA.load_trace(HAND), "t")
    # a chip: flights 100-206, 300-386 twice, and the psum's 2
    assert sp.comm_us == 10 * 2 * (2 * (106 + 86) + 2)
    assert sp.compute_us == 10 * 2 * 2 * (100 + 86 + 94 + 19)
    # comm hidden under compute: the gather under fusion.1's tail... no:
    # under fusion.378 (86) and the permute under fusion.3 (19)
    assert sp.overlap_us == 10 * 2 * 2 * (86 + 19)
    assert sp.overlap_fraction == sp.overlap_us / sp.comm_us
    assert sp.comm_fraction == sp.comm_us / (sp.comm_us + sp.compute_us)
    assert sp.top_comm[0] == ("async-collective-start", 4240.0)
    assert sp.top_compute[0] == ("fusion.1", 4000.0)
    assert "overhead" in sp.report("t")


def test_no_trace_returns_none(tmp_path):
    assert TA.split_from_trace(str(tmp_path)) is None
    assert TA.latest_xplane_file(str(tmp_path)) is None


def test_split_from_real_trace(tmp_path, mesh8):
    """End-to-end on the CPU simulator's own ``.xplane.pb``: trace a
    collective-heavy jit and recover a split with nonzero comm; a thunk's
    event is named after its instruction and has no path and no bytes."""
    f = jax.jit(C.smap(lambda x: C.all_reduce(x @ x.T, "dp"),
                       mesh8, P("dp"), P()))
    x = jnp.ones((8, 128, 128))
    jax.block_until_ready(f(x))  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(3):
        out = f(x)
    jax.block_until_ready(out)
    jax.profiler.stop_trace()
    sp = TA.split_from_trace(str(tmp_path))
    assert sp is not None and sp.trace_file.endswith(".xplane.pb")
    assert sp.comm_us > 0
    assert sp.compute_us > 0
    assert 0.0 < sp.comm_fraction < 1.0
    planes = TA.collective_events(sp.trace_file)
    evs = [e for p in planes.values() for e in p]
    assert len(evs) == 3 * 8 and {e.kind for e in evs} == {"all_reduce"}
    assert {(e.bytes, e.scope, e.phase) for e in evs} == {(None, None, "fwd")}
    hlo = f.lower(x).compile().as_text()
    with_text = TA.collective_events(sp.trace_file, hlo_text=hlo)
    assert {(e.bytes, e.bytes_source) for p in with_text.values()
            for e in p} == {(128 * 128 * 4, "hlo")}


# ------------------------------------------ one step of the four-chip cell

def test_the_chip_step_reads_the_parameter_arithmetic():
    """One traced step of SmolLM3-3B whole on the 2x2 (chip 2): the
    gathers move every parameter forward and the layers' again backward,
    the reduce-scatters every gradient; what the trace adds is XLA's
    padding of three fused reduce-scatters and its halo permutes."""
    with open(CHIP_STEP) as f:
        doc = json.load(f)
    evs = TA.collective_events(CHIP_STEP, group=doc["group"])[
        "/device:TPU:2"]
    assert {e.bytes_source for e in evs} == {"trace"}
    layer = 2 * 2048 * 2048 + 2 * 2048 * 512 + 3 * 2048 * 11008 + 2 * 2048
    root = 128256 * 2048 + 2048
    logical = {"all_gather": 2 * (root + 36 * layer) + 2 * 36 * layer,
               "reduce_scatter": 2 * (root + 36 * layer)}
    got = {k: sum(e.bytes for e in evs if e.kind == k) for k in logical}
    assert got["all_gather"] == pytest.approx(logical["all_gather"],
                                              rel=1e-4)
    # 11008 + 2 x 2048 rows a layer and the embedding are padded to a
    # multiple the fused kernel wants, and two norms ride as all-reduces
    assert 1.0 < got["reduce_scatter"] / logical["reduce_scatter"] < 1.006
    bus = sum(e.bytes * bus_factor(e.kind, 4) for e in evs)
    arithmetic = 0.75 * sum(logical.values())
    assert arithmetic == pytest.approx(13.444e9, rel=1e-3)
    assert 1.0 < bus / arithmetic < 1.02
    by_kind = {k: sum(e.exposed_ns for e in evs if e.kind == k) / 1e6
               for k in TA.KINDS}
    # the reduce-scatters are what the step waits on
    assert by_kind["reduce_scatter"] == pytest.approx(78.6, abs=1.0)
    assert by_kind["all_gather"] == pytest.approx(6.75, abs=0.3)
    assert by_kind["collective_permute"] + by_kind["all_reduce"] < 1.0
    assert {(e.scope, e.phase) for e in evs
            if e.instruction.startswith("reduce_scatter.")} \
        == {("fsdp_layer_gather", "bwd")}


def test_collective_placement_schedule_shapes(mesh8):
    """The HLO schedule-shape parser (behind scripts/overlap_analysis.py)
    must recover the reshard knob's defining difference: reshard=True
    re-gathers per layer INSIDE the scan while-body (ZeRO-3), while
    reshard=False hoists every gather out of the loop (ZeRO-2) —
    reference ``fsdp/train_fsdp.py:84-88``."""
    from distributed_training_sandbox_tpu.models import transformer as T
    from distributed_training_sandbox_tpu.parallel import fsdp

    cfg = T.TINY_LM
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    shards = fsdp.shard_params_fsdp(params, mesh8)
    opt = fsdp.init_fsdp_opt_state(shards)
    ids = jnp.zeros((8, 16), jnp.int32)

    def placement(reshard):
        step = fsdp.make_fsdp_train_step(shards, cfg, mesh8, donate=False,
                                         reshard_after_forward=reshard)
        txt = step.lower(shards, opt, (ids, ids)).compile().as_text()
        return TA.collective_placement(txt)

    z3 = placement(True)
    z2 = placement(False)
    # 9 stacked layer leaves gather in-loop under reshard; none without
    assert z3["all-gather"]["in_loop_body"] >= 9, z3
    assert z2["all-gather"]["in_loop_body"] == 0, z2
    assert z2["all-gather"]["hoisted"] >= 11, z2
    # the backward reduce-scatters follow the same placement
    assert z3["reduce-scatter"]["in_loop_body"] >= 9, z3
    assert z2["reduce-scatter"]["in_loop_body"] == 0, z2
