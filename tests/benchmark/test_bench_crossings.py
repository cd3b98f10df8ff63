"""The crossings helper on a hand-made trace whose gaps have known wake,
read, host-work and launch parts; what it says of the programs' names
beside ``reduce_trace.alias_modules``; the readers built on it, which find
nothing in an untraced run, in a window without a round and on a program
without the counters; and the twelve entries, found by their names.  CPU
only: interval
arithmetic, no device metric."""

import copy
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import harness, reduce_trace as R  # noqa: E402
from benchmarks.layer_metrics import _crossings as C  # noqa: E402
from benchmarks.layer_metrics import _scopes as S  # noqa: E402

US = 1e3    # the fixture's unit, in ns
FIXTURE = ROOT / "benchmarks/fixtures/crossings_small.json"
PRE, DEC = "jit__unknown(1)", "jit__unknown(2)"
IDLE = ["round_idle_wake_ms", "round_idle_read_ms", "round_idle_hostwork_ms",
        "round_idle_launch_latency_ms"]
COUNTS = ["d2h_reads_per_round", "h2d_puts_per_round"]
NEW = [n + suffix for suffix in ("", "_tput") for n in IDLE + COUNTS]
BY_HAND = {"wake": 195 * US, "read": 310 * US, "hostwork": 490 * US,
           "launch_latency": 220 * US}


def _reduce(obj) -> C.Crossings | None:
    attrs = {(n, float(s)): a for n, s, a in obj.get("span_attrs", [])}
    return C.reduce(S.ScopedRaw.from_json(obj), attrs)


@pytest.fixture(scope="module")
def obj():
    return json.loads(FIXTURE.read_text())


@pytest.fixture(scope="module")
def tab(obj):
    return _reduce(obj)


def _reader(name):
    return harness.find_module("layer_metrics", name)


def _ctx(monkeypatch, path, stats=None, launches=None):
    """A traced run whose ``.xplane.pb`` is the JSON at ``path``."""
    monkeypatch.setattr(R, "find_xplane", lambda d: str(path))
    S._TABLES.pop(str(path), None)
    C._TABLES.pop(str(path), None)
    raw = S.load(str(path))
    red = R.reduce(R.RawTrace(
        devices={p: {"ops": [e[:3] for e in d["ops"]],
                     "modules": d["modules"], "async": []}
                 for p, d in raw.devices.items()},
        host=[e for t in raw.threads for e in t if e[0] == R.WINDOW_SPAN]))
    return SimpleNamespace(
        trace=red, chips=1,
        counters={"stats": stats or {},
                  "program_launches": launches or {"decode": 3,
                                                   "prefill": 1}})


def test_the_four_parts_are_the_hand_values_and_sum_to_the_rounds_idle(tab):
    assert tab.rounds == 1          # the round after the window is not one
    assert tab.parts == pytest.approx(BY_HAND)
    scopes = S.reduce(S.load(str(FIXTURE)))
    in_round = scopes.idle_ns(lambda st: S.ROUND in st)
    assert tab.total_ns == pytest.approx(in_round) \
        and in_round == pytest.approx(1215 * US)
    # a gap wholly inside pump/sync_every is wake; both bubbles lie inside
    # one program's event and stay in the part the rules give them
    assert tab.bubbles == pytest.approx(
        {"wake": 5 * US, "read": 0, "hostwork": 10 * US,
         "launch_latency": 0})


def test_a_burst_of_three_reads_is_counted_by_its_arrays(tab):
    # the prefill's one read is wake (its sync was open when the chip fell
    # idle): only the burst's three, and the three occupancy scalars the
    # pump resolved before them, are reads of an idle chip
    assert tab.reads == 3 + 3
    assert tab.parts["read"] == pytest.approx((10 + 300) * US)
    assert tab.moved == {"serve/prefill_stage": (1, 4, 100),
                         "serve/prefill_sync": (1, 1, 4),
                         "serve/burst_stage": (1, 5, 104),
                         "pump/resolve": (1, 3, 0),
                         "serve/burst_sync": (1, 3, 24)}
    # what is left to the host's own work, by the span it was in
    assert tab.hostwork_by_span == pytest.approx({
        "serve/round": 200 * US, "serve/bookkeep": 140 * US,
        "serve/burst_stage": 60 * US, "serve/prefill_stage": 40 * US,
        "serve/burst_dispatch": 20 * US, "serve/admit": 20 * US,
        "serve/prefill_dispatch": 10 * US})
    assert sum(tab.hostwork_by_span.values()) \
        == pytest.approx(tab.parts["hostwork"])
    assert tab.carried == {"serve/burst_dispatch live": 2.0,
                           "serve/prefill_dispatch rows": 5.0}


def test_a_gap_goes_to_the_launch_whose_program_ended_it(tab):
    """Decode step 1 is launched (1780) before the chip begins step 0
    (1800): the gap is step 0's, by the pairing of module events with
    launch spans, not the last launch's."""
    assert tab.by_launch == pytest.approx({
        ("prefill", 0): 200 * US, ("decode", 0): 300 * US,
        C.NO_LAUNCH: 715 * US})
    assert tab.programs == {PRE: "prefill", DEC: "decode"}
    assert tab.launches == {"prefill": 1, "decode": 3}     # the window's
    assert tab.modules == {PRE: (1, 200 * US), DEC: (3, 400 * US)}


def test_unpaired_launches_fall_back_to_the_last_launch_before_the_gaps_end(
        obj):
    """One module event short (as a speculative engine's eager calls make
    it one long): no naming, and a gap goes to the last launch span that
    begins before its end and is open after its start."""
    short = copy.deepcopy(obj)
    short["devices"]["/device:TPU:0"]["modules"].pop()
    tab = _reduce(short)
    assert tab.programs == {}
    assert tab.clock == (0.0, 0.0)      # and nothing to bound the clocks by
    assert tab.parts == pytest.approx(
        {**BY_HAND, "hostwork": 560 * US, "launch_latency": 150 * US})
    assert tab.by_launch == pytest.approx({
        ("prefill", 0): 200 * US, ("decode", 1): 300 * US,
        C.NO_LAUNCH: 715 * US})
    assert "not paired" in tab.report()


def test_the_clocks_offset_is_bounded_by_causality_and_split_at_its_middle(
        obj, tab):
    """A profiler aligns the chip's clock with the host's to about a
    millisecond.  No program begins before its launch and no wait ends
    before the program it waited for: the fixture's clocks agree, and
    bound the chip's lead to +-90 us; with every chip event 500 us late
    the bounds move by as much."""
    assert tab.clock == (-90 * US, 90 * US)
    late = copy.deepcopy(obj)
    for lines in late["devices"].values():
        for key in ("ops", "modules"):
            for e in lines[key]:
                e[1] += 500 * US
    shifted = _reduce(late)
    assert shifted.clock == (410 * US, 590 * US)
    # every gap's inside splits as before; the round's own edges stay the
    # host's (the sum stays ``round_idle_ms``), so the first gap gains 500
    # us of host work before the round's first span and the last, cut at
    # the round's end, loses its book-keeping and 200 of its 300 us of reads
    assert shifted.parts == pytest.approx(
        {"wake": 195 * US, "read": 110 * US, "launch_latency": 220 * US,
         "hostwork": (70 + 500 + 110 + 10) * US})
    assert shifted.by_launch[("decode", 0)] == pytest.approx(300 * US)
    assert "leads the host's by 0.410 to 0.590 ms" in shifted.report()


def test_report_names_every_part_and_program(tab):
    text = tab.report()
    for word in C.PARTS + ("ms a read", "decode k=0", "prefill k=0",
                           "serve/burst_sync", "live", "rows",
                           f"{PRE} = prefill"):
        assert word in text, word


def _equal_counts() -> dict:
    """Two chunks and two decode steps in one round: by launch count the
    two programs cannot be told apart, and ``alias_modules`` gives the
    first label to the first module it meets."""
    def span(name, a, b):
        return [name, a * US, (b - a) * US]
    starts = {"prefill": (10, 30), "decode": (50, 70)}
    main = [span("bench/window", 0, 1000), span("serve/round", 0, 1000)]
    attrs = []
    for prog, at in starts.items():
        for k, a in enumerate(at):
            main.append(span(C.LAUNCH_SPAN, a, a + 10))
            attrs.append([C.LAUNCH_SPAN, a * US, {"program": prog, "k": k}])
    mods = [(PRE, 100), (PRE, 200), (DEC, 300), (DEC, 400)]
    return {"devices": {"/device:TPU:0": {
        "ops": [[f"fusion.{i}", a * US, 100 * US, ""]
                for i, (_, a) in enumerate(mods)],
        "modules": [span(m, a, a + 100) for m, a in mods]}},
        "threads": [main], "span_attrs": attrs}


def test_equal_launch_counts_get_the_line_and_the_span_derived_names(
        monkeypatch, tmp_path, capsys):
    path = tmp_path / "equal.json"
    path.write_text(json.dumps(_equal_counts()))
    launches = {"decode": 2, "prefill": 2}
    ctx = _ctx(monkeypatch, path, launches=launches)
    assert R.alias_modules(ctx.trace, launches) == {PRE: "decode",
                                                    DEC: "prefill"}
    tab = C.table(ctx)
    assert tab.programs == {PRE: "prefill", DEC: "decode"}
    err = capsys.readouterr().err
    assert "program names disagree" in err and "_programs.py" in err
    assert f"{PRE} is 'prefill' by its launch spans and 'decode'" in err
    # the new readers do not depend on the guess and still report
    assert _reader("round_idle_launch_latency_ms").read(ctx) \
        == pytest.approx(0.090)
    assert capsys.readouterr().err == ""            # the table, once
    # the command line says the same of the trace alone
    assert C.main([str(path)]) == 0
    assert "program names disagree" in capsys.readouterr().err


def test_names_that_agree_print_no_line(monkeypatch, capsys):
    ctx = _ctx(monkeypatch, FIXTURE)
    assert C.table(ctx).swapped(ctx.counters["program_launches"],
                                ctx.trace) is None
    err = capsys.readouterr().err
    assert "by crossing" in err and "disagree" not in err


def test_readers_read_the_parts_and_the_counters(monkeypatch):
    stats = {"rounds": 4, "d2h_reads": 18, "h2d_puts": 29, "launches": 21}
    ctx = _ctx(monkeypatch, FIXTURE, stats=stats)
    want = {"round_idle_wake_ms": 0.195, "round_idle_read_ms": 0.310,
            "round_idle_hostwork_ms": 0.490,
            "round_idle_launch_latency_ms": 0.220,
            "d2h_reads_per_round": 4.5, "h2d_puts_per_round": 7.25}
    for name, value in want.items():
        for suffix in ("", "_tput"):
            assert _reader(name + suffix).read(ctx) == pytest.approx(value)
    assert sum(want[n] for n in IDLE) \
        == pytest.approx(_reader("round_idle_ms").read(ctx))
    # the new nesting leaves the older split where it was: a launch span
    # is a launch-class span
    assert S.LAUNCH.match(C.LAUNCH_SPAN) and not S.READBACK.match(
        C.LAUNCH_SPAN)
    launch = _reader("round_idle_launch_ms").read(ctx)
    readback = _reader("round_idle_readback_ms").read(ctx)
    # under serve/round itself: [2700, 2900) of the fixture
    assert launch + readback + 0.200 == pytest.approx(1.215)


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_in_an_untraced_run(name):
    ctx = SimpleNamespace(trace=None, chips=1,
                          counters={"stats": {"rounds": 3},
                                    "program_launches": {}})
    assert _reader(name).read(ctx) is None


def test_readers_find_nothing_without_a_round_or_without_the_counters(
        monkeypatch, tmp_path, obj):
    """The parent commit's trace holds no ``serve/launch_dispatch`` and its
    engine no crossing counters; a window without ``serve/round`` has no
    round to split.  Nothing raises; what has nothing to read is left
    out."""
    bare = copy.deepcopy(obj)
    bare["threads"] = [[e for e in t if not e[0].startswith("serve/")]
                       for t in bare["threads"]]
    path = tmp_path / "no_round.json"
    path.write_text(json.dumps(bare))
    ctx = _ctx(monkeypatch, path, stats={"rounds": 3, "admit_s": 0.1})
    assert {n: _reader(n).read(ctx) for n in NEW} == dict.fromkeys(NEW)
    assert C.main([str(path)]) == 1

    parent = copy.deepcopy(obj)          # rounds, but no launch is a span
    parent["threads"] = [[e for e in t if e[0] != C.LAUNCH_SPAN]
                         for t in parent["threads"]]
    path = tmp_path / "parent.json"
    path.write_text(json.dumps(parent))
    ctx = _ctx(monkeypatch, path, stats={"rounds": 3, "admit_s": 0.1})
    assert {n: _reader(n).read(ctx) for n in NEW} == dict.fromkeys(NEW)
    assert _reader("round_idle_ms").read(ctx) == pytest.approx(1.215)


def test_the_twelve_entries_list_their_cells_and_what_they_are_judged_on():
    """The twelve by name, wherever they stand in ``per_layer``: each is
    listed first for the cell it was accepted in, and a serving cell judged
    on the same metric may join its list."""
    bm = harness.load_benchmark()
    entries = {m["name"]: m for m in bm["per_layer"]}
    judged_on = {m["name"]: set(m["workloads"]) for m in bm["end_to_end"]
                 if "workloads" in m}
    for name in NEW:
        e, tput = entries[name], name.endswith("_tput")
        assert e["workloads"][0] == ("serve-doc-batch" if tput
                                     else "serve-chat")
        assert set(e["workloads"]) <= judged_on[e["moves"]]
        assert e["moves"] == ("serve_tokens_per_s" if tput
                              else "serve_tpot_p50_ms")
        assert e["layer"] == "scheduler" and e["better"] == "lower"
        counter = name.removesuffix("_tput") in COUNTS
        assert e["source"] == ("program_counter" if counter
                               else "program_span")
        assert e["unit"] == (name.split("_")[1] if counter else "ms")
    for cell, suffix in (("serve-chat", ""), ("serve-doc-batch", "_tput")):
        listed = {m.name for m in harness.load_cell(cell).per_layer}
        assert {n + suffix for n in IDLE + COUNTS} <= listed
    # the six accepted backlog cells split their rounds' idle the same
    # way; a training cell has no round to split
    for cell in ("serve-doc-batch", "serve-mla-moe-longgen",
                 "serve-hybrid-rollout", "serve-hybrid-moe-longgen",
                 "serve-swa-moe-mixedlen", "serve-ssm-moe-sessions"):
        listed = {m.name for m in harness.load_cell(cell).per_layer}
        assert {n + "_tput" for n in IDLE + COUNTS} <= listed, cell
    assert not set(NEW) & {
        m.name for m in harness.load_cell("train-dense-8k").per_layer}
