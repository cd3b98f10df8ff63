"""The scope and span helper on a small hand-made trace, against answers
computed by hand; its protobuf reading on a hand-encoded ``XSpace``; and the
readers built on it, which find nothing on a program without the names.
CPU only: interval arithmetic, no device metric."""

import struct
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import harness, reduce_trace as R  # noqa: E402
from benchmarks.layer_metrics import _scopes as S  # noqa: E402

US = 1e3    # the fixture's unit, in ns
FIXTURE = ROOT / "benchmarks/fixtures/scopes_small.json"
STEP, ENGINE = "jit_step", "jit__unknown(7)"


@pytest.fixture(scope="module")
def tab():
    return S.reduce(S.load(str(FIXTURE)))


@pytest.mark.parametrize("path,want", [
    ("jit(step)/forward_backward/jvp(mlp)/dot_general", "mlp"),
    ("jit(step)/forward_backward/transpose(jvp(attn_qkv))/mul", "attn_qkv"),
    ("jit(step)/forward_backward/transpose(jvp())/while/body/closed_call/"
     "checkpoint/rematted_computation/mlp/dot_general", "mlp"),
    ("jit(step)/forward_backward/jvp()/while/body/attn_core/"
     "vmap(jit(_splash_attention))/pallas_call", "attn_core"),
    ("jit(step)/forward_backward/jvp(mlp)/fsdp_layer_gather/all_gather",
     "fsdp_layer_gather"),                      # the innermost of two
    ("jit(<unknown>)/kv_gather/gather", "kv_gather"),
    ("jit(step)/forward_backward/jvp()/while", None),   # a container only
    ("jit(sample_tokens)/resample/add", None),  # whole words, not substrings
    ("", None), (None, None),
])
def test_innermost_catalogue_name(path, want):
    assert S.innermost(path) == want


def test_catalogue_is_the_programs():
    from distributed_training_sandbox_tpu.utils import profiling
    assert S.CATALOGUE == profiling.SCOPES


def test_window_and_spans_counted_inside_it(tab):
    assert tab.window == (1000 * US, 2000 * US)
    # the round that begins after the window is not counted
    assert tab.span_counts == {
        "serve/round": 1, "serve/admit": 1, "serve/burst_stage": 1,
        "serve/burst_dispatch": 1, "pump/sync_every": 1,
        "serve/burst_sync": 1, "serve/bookkeep": 1}


def test_self_time_per_program_and_scope(tab):
    chip0, chip1 = tab.chips
    assert chip0.self_ns == pytest.approx({
        (STEP, "embed"): 20 * US,               # cut to the window
        (STEP, "mlp"): 180 * US,                # forward 80 + remat 150 - 50
        (STEP, "fsdp_layer_gather"): 50 * US,   # nested in the remat fusion
        (STEP, "attn_qkv"): 100 * US,           # a backward path
        (STEP, "attn_core"): 100 * US,
        (STEP, S.NO_SCOPE): 50 * US,            # a path with no name
        (STEP, "opt_step"): 100 * US,
        (ENGINE, "kv_gather"): 100 * US,
        (ENGINE, "attn_core"): 50 * US,
        (ENGINE, S.NO_SCOPE): 100 * US})        # an op with no path
    assert chip1.self_ns == {(STEP, "mlp"): 1000 * US}
    # the while is a container: busy is the sum of the self times
    assert sum(chip0.self_ns.values()) == pytest.approx(850 * US)


def test_scope_sums_are_means_over_chips_and_split_by_program(tab):
    assert tab.scope_ns(("mlp",)) == pytest.approx(590 * US)
    assert tab.scope_ns(("attn_qkv", "mlp")) == pytest.approx(640 * US)
    assert tab.scope_ns(("attn_core",)) == pytest.approx(75 * US)
    assert tab.scope_ns(("attn_core",), program=ENGINE) \
        == pytest.approx(25 * US)
    assert tab.busy_self_ns() == pytest.approx(925 * US)
    assert tab.scope_ns((S.NO_SCOPE,)) == pytest.approx(75 * US)


def test_idle_goes_to_the_stack_open_on_the_main_thread(tab):
    chip0, chip1 = tab.chips
    assert chip1.idle_ns == 0 and chip1.idle_by_stack == {}
    assert tab.idlest is chip0 and chip0.idle_ns == pytest.approx(150 * US)
    rnd = ("serve/round",)
    assert chip0.idle_by_stack == pytest.approx({
        rnd: 5 * US,                                    # [1600, 1605)
        rnd + ("serve/burst_stage",): 25 * US,          # [1605, 1630)
        rnd + ("serve/burst_dispatch",): 10 * US,       # [1630, 1640)
        rnd + ("serve/burst_dispatch", "pump/sync_every"): 10 * US,
        rnd + ("serve/burst_sync",): 10 * US,           # [1900, 1910)
        # bookkeep outlasts its round by a tick and is cut to it
        rnd + ("serve/bookkeep",): 30 * US,             # [1910, 1940)
        # the producer thread's prefetch/stage takes no gap
        (): 60 * US})                                   # [1940, 2000)
    assert tab.idle_by_span() == pytest.approx({
        "serve/round": 5 * US, "serve/burst_stage": 25 * US,
        "serve/burst_dispatch": 10 * US, "pump/sync_every": 10 * US,
        "serve/burst_sync": 10 * US, "serve/bookkeep": 30 * US,
        S.NO_SPAN: 60 * US})


def test_segments_nest_and_close_in_order():
    got = S.segments([("a", 0, 100), ("b", 10, 30), ("c", 20, 10),
                      ("d", 60, 50), ("e", 200, 10)])
    assert got == [
        (0, 10, ("a",)), (10, 20, ("a", "b")), (20, 30, ("a", "b", "c")),
        (30, 40, ("a", "b")), (40, 60, ("a",)), (60, 100, ("a", "d")),
        (200, 210, ("e",))]


def test_report_names_every_scope_and_span(tab):
    text = tab.report({ENGINE: "decode"})
    for word in ("mlp", "fsdp_layer_gather", S.NO_SCOPE, "decode",
                 "serve/bookkeep", "pump/sync_every", S.NO_SPAN):
        assert word in text


# ------------------------------------------------------ the raw protobuf

def _varint(n: int) -> bytes:
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _f(num: int, value) -> bytes:
    """One protobuf field: an int as a varint, bytes length-delimited."""
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _plane(name: str, stat_names: dict, events: dict) -> bytes:
    body = _f(2, name.encode())
    for key, text in stat_names.items():
        body += _f(5, _f(1, key) + _f(2, _f(1, key) + _f(2, text.encode())))
    for key, (ev_name, stats) in events.items():
        meta = _f(1, key) + _f(2, ev_name.encode()) \
            + b"".join(_f(5, st) for st in stats)
        body += _f(4, _f(1, key) + _f(2, meta))
    return _f(1, body)


def test_op_paths_reads_the_event_metadata_of_device_planes():
    names = {1: "tf_op", 2: "flops", 3: "jit(step)/opt_step/mul:"}
    dev = _plane("/device:TPU:0", names, {
        7: ("%fusion.1 = bf16[4] fusion()", [
            _f(1, 2) + _f(3, 99),                          # flops: skipped
            _f(1, 2) + _varint(2 << 3 | 1) + struct.pack("<d", 1.5),
            _f(1, 1) + _f(5, b"jit(step)/forward_backward/jvp(mlp)/dot:")]),
        8: ("%fusion.2 = bf16[4] fusion()", [_f(1, 1) + _f(7, 3)]),  # interned
        9: ("%fusion.3 = bf16[4] fusion()", [_f(1, 2) + _f(3, 5)])})  # no path
    host = _plane("/host:CPU", names, {
        1: ("serve/round", [_f(1, 1) + _f(5, b"not/a/device/op")])})
    got = S.op_paths(dev + host + _f(3, b"a warning"))
    assert got == {"/device:TPU:0": {
        "%fusion.1 = bf16[4] fusion()":
            "jit(step)/forward_backward/jvp(mlp)/dot",
        "%fusion.2 = bf16[4] fusion()": "jit(step)/opt_step/mul"}}


# --------------------------------------------------------------- readers

def _reader(name):
    return harness.find_module("layer_metrics", name)


def _ctx(monkeypatch, path, counters, **kw):
    """A traced run whose ``.xplane.pb`` is the JSON at ``path``."""
    monkeypatch.setattr(R, "find_xplane", lambda d: str(path))
    S._TABLES.pop(str(path), None)
    raw = S.load(str(path))
    red = R.reduce(R.RawTrace(
        devices={p: {"ops": [e[:3] for e in d["ops"]],
                     "modules": d["modules"], "async": []}
                 for p, d in raw.devices.items()},
        host=[e for t in raw.threads for e in t if e[0] == R.WINDOW_SPAN]))
    return SimpleNamespace(trace=red, counters=counters, **kw)


TRAIN_FIELDS = {"hidden_size": 2048, "num_attention_heads": 16,
                "num_key_value_heads": 4, "head_dim": 128,
                "intermediate_size": 11008, "num_hidden_layers": 8}
COUNTS = harness.find_module("counts", "dense_gqa")


def test_training_readers(monkeypatch, capsys):
    c = {"steps": 2, "tokens": 2 * 32768}
    ctx = _ctx(monkeypatch, FIXTURE, c, chips=2, fields=TRAIN_FIELDS,
               counts=COUNTS, peaks={"bf16_flops_per_s": 197e12})
    assert _reader("proj_mlp_ms").read(ctx) == pytest.approx(0.640 / 2)
    assert "serve/bookkeep" in capsys.readouterr().err    # the table, once
    assert _reader("loss_head_ms").read(ctx) is None      # no such scope
    assert _reader("optimizer_ms").read(ctx) == pytest.approx(0.050 / 2)
    assert capsys.readouterr().err == ""
    assert _reader("scope_unattributed_pct").read(ctx) \
        == pytest.approx(100 * 75 / 925)
    roof = _reader("proj_mlp_roofline")
    assert ctx.counts.proj_mlp_weight_count(TRAIN_FIELDS) == 8 * 78_118_912
    need = 6.0 * 8 * 78_118_912 * 32768 / 2        # per chip and step
    assert roof.read(ctx) == pytest.approx(100 * need / 197e12 / 0.320e-3)


def test_serving_readers(monkeypatch):
    c = {"program_launches": {"decode": 1},
         "stats": {"admitted": 4, "queue_wait_s": 0.5}}
    ctx = _ctx(monkeypatch, FIXTURE, c, chips=1)
    assert _reader("decode_attn_ms").read(ctx) == pytest.approx(0.075)
    assert _reader("decode_attn_ms_tput").read(ctx) == pytest.approx(0.075)
    assert _reader("prefill_attn_ms_tput").read(ctx) is None  # no launches
    assert _reader("round_idle_ms").read(ctx) == pytest.approx(0.090)
    assert _reader("round_idle_launch_ms").read(ctx) == pytest.approx(0.045)
    assert _reader("round_idle_readback_ms").read(ctx) \
        == pytest.approx(0.040)
    assert _reader("round_idle_unattributed_pct").read(ctx) \
        == pytest.approx(40.0)
    assert _reader("sched_queue_wait_ms").read(ctx) == pytest.approx(125.0)


NEW = ["proj_mlp_ms", "proj_mlp_roofline", "loss_head_ms", "optimizer_ms",
       "scope_unattributed_pct", "decode_attn_ms", "decode_attn_ms_tput",
       "prefill_attn_ms_tput", "round_idle_ms", "round_idle_launch_ms",
       "round_idle_readback_ms", "round_idle_unattributed_pct",
       "sched_queue_wait_ms"]


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_in_an_untraced_run(name):
    ctx = SimpleNamespace(trace=None, chips=1, fields=TRAIN_FIELDS,
                          counts=COUNTS,
                          counters={"steps": 2, "tokens": 8, "stats": {},
                                    "program_launches": {}})
    assert _reader(name).read(ctx) is None


def test_readers_find_nothing_on_a_program_without_the_names(monkeypatch,
                                                             tmp_path):
    """The parent commit's trace: paths without a catalogue name below the
    strategy's, no ``serve/`` span, no admission counter.  Nothing raises;
    what has nothing to read is left out."""
    import json
    raw = S.load(str(FIXTURE))
    for lines in raw.devices.values():
        lines["ops"] = [(n, s, d, "jit(step)/forward_backward/jvp()/while")
                        for n, s, d, _ in lines["ops"]]
    raw.threads = [[e for e in t if e[0].startswith(R.HOST_PREFIX)]
                   for t in raw.threads]
    parent = tmp_path / "parent.json"
    parent.write_text(json.dumps(raw.to_json()))
    c = {"steps": 2, "tokens": 2 * 32768,
         "program_launches": {"decode": 1},
         "stats": {"rounds": 1, "admit_s": 0.1, "bookkeep_s": 0.1}}
    ctx = _ctx(monkeypatch, parent, c, chips=2, fields=TRAIN_FIELDS,
               counts=COUNTS, peaks={"bf16_flops_per_s": 197e12})
    got = {n: _reader(n).read(ctx) for n in NEW}
    assert got.pop("scope_unattributed_pct") == pytest.approx(100.0)
    assert set(got.values()) == {None}
