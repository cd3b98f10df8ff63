"""The program names its own work: device scopes in the model step and both
engine programs (metadata only: the programs do not change), and host spans
that are profiler annotations whether or not a telemetry run is wired."""

import contextlib
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_training_sandbox_tpu.analysis.pitfalls import lint_source
from distributed_training_sandbox_tpu.models import transformer as T
from distributed_training_sandbox_tpu.parallel import fsdp
from distributed_training_sandbox_tpu.runtime import DevicePrefetcher, StepPump
from distributed_training_sandbox_tpu.serving import ServingEngine
from distributed_training_sandbox_tpu.telemetry import (
    TelemetryRun, maybe_span, read_spans)
from distributed_training_sandbox_tpu.utils import make_mesh, profiling
from tests.serving_blocks import BLOCKS, FIELDS, make

TINY = T.TransformerConfig(
    vocab_size=256, hidden_size=64, intermediate_size=128,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    nope_interval=2, loss_vocab_chunk=64, dtype=jnp.float32)

MODEL_SCOPES = {"embed", "attn_qkv", "attn_core", "attn_out", "mlp",
                "loss_head"}
STEP_SCOPES = MODEL_SCOPES | {"fsdp_layer_gather", "fsdp_root_gather",
                              "loss_mean", "grad_mean", "opt_step"}
ENGINE_SCOPES = {"embed", "attn_qkv", "kv_write", "kv_gather", "attn_core",
                 "attn_out", "mlp", "sample"}

# in engine order: what one round of one single-chunk request opens, at
# ``sync_every`` 2: every call of a compiled program is a span of its own
ROUND_SPANS = ["serve/round", "serve/admit", "serve/prefill_stage",
               "serve/prefill_dispatch", "serve/launch_dispatch",
               "serve/prefill_sync", "serve/bookkeep", "serve/burst_stage",
               "serve/burst_dispatch", "serve/launch_dispatch",
               "serve/launch_dispatch", "serve/burst_sync", "serve/bookkeep"]

# the gated delta-rule hybrid of ``tests/test_gdn_hybrid.py``: its prefill
# program takes a fifth argument and its decode program counts on the device
HYBRID = T.TransformerConfig(**FIELDS["gdn_hybrid"], dtype=jnp.float32,
                             remat=False)


def _lower_train_step():
    mesh = make_mesh({"dp": 2}, devices=jax.devices()[:2], register=False)
    params = T.init_params(jax.random.key(0), TINY)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                             fsdp.fsdp_specs(params),
                             is_leaf=lambda x: isinstance(x, P))
    shards = jax.device_put(params, shardings)
    step = fsdp.make_fsdp_train_step(shards, TINY, mesh)
    batch = jax.device_put((jnp.zeros((2, 32), jnp.int32),) * 2,
                           NamedSharding(mesh, P("dp")))
    return step.lower(shards, fsdp.init_fsdp_opt_state(shards), batch)


@functools.cache
def _weights(cfg):
    """``cfg``'s weights, made once a module run (a config hashes by its
    fields)."""
    return jax.tree.map(lambda x: (x * 3.0).astype(x.dtype),
                        T.init_params(jax.random.key(0), cfg))


@functools.cache
def _block(block: str):
    return make(block)


def _engine(cfg=TINY, **kw):
    return ServingEngine(_weights(cfg), cfg, max_batch=2, page_size=8,
                         max_seq_len=32, prefill_chunk=8, sync_every=2, **kw)


def _lower_engine(which: str):
    eng = _engine()
    B, Pn = eng.max_batch, eng.pages_per_request
    z = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
    if which == "decode":
        return eng._decode.lower(eng.pool.bufs, eng._params, z(B, Pn), z(B),
                                 z(B), z(B), jnp.zeros((B,), bool),
                                 eng._carry_zero)
    return eng._prefill.lower(eng.pool.bufs, eng._params_pre, z(1, Pn),
                              z(1, 8), jnp.int32(0), jnp.int32(5))


LOWERINGS = {"train_step": (_lower_train_step, STEP_SCOPES),
             "decode": (lambda: _lower_engine("decode"), ENGINE_SCOPES),
             "prefill": (lambda: _lower_engine("prefill"), ENGINE_SCOPES)}


def _scope_names(lowered) -> set[str]:
    """Every identifier in the op-name paths of a lowering's locations."""
    paths = re.findall(r'loc\("([^"]+)"', lowered.as_text(debug_info=True))
    return {tok for p in paths for tok in re.split(r"[^A-Za-z0-9_]+", p)}


def _strip_metadata(hlo: str) -> str:
    """Compiled HLO text without op metadata and without the source-location
    tables that metadata indexes (they name the caller's line)."""
    hlo = re.sub(r"\n(FileNames|FunctionNames|FileLocations|StackFrames)\n"
                 r"(?:\d+ .*\n)+", "\n", hlo)
    return re.sub(r",? ?metadata=\{[^}]*\}", "", hlo)


@pytest.mark.parametrize("program", sorted(LOWERINGS))
def test_catalogue_scopes_are_in_the_lowered_program(program):
    lower, want = LOWERINGS[program]
    assert want <= set(profiling.SCOPES)
    names = _scope_names(lower())
    assert want <= names, sorted(want - names)


@pytest.mark.parametrize("program", sorted(LOWERINGS))
def test_scopes_write_metadata_only(program, monkeypatch):
    """The compiled program with ``metadata={...}`` stripped is the same
    text with ``jax.named_scope`` patched out: no scope moves an op."""
    lower, _ = LOWERINGS[program]
    with_scopes = lower()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without = lower()
    assert "mlp" not in _scope_names(without)
    assert with_scopes.as_text() == without.as_text()
    assert _strip_metadata(with_scopes.compile().as_text()) \
        == _strip_metadata(without.compile().as_text())


def test_engine_programs_keep_no_name_of_their_own():
    """The benchmark's readers find the two engine programs as the UNNAMED
    modules of a trace, by launch count; naming them is a later PR's."""
    for which in ("decode", "prefill"):
        assert "unknown" in _lower_engine(which).as_text().split("\n")[0]


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_engine_programs_never_reach_the_remat_path(which, monkeypatch):
    """Neither engine program calls the splash attention, the remat
    policy mapping or ``jax.checkpoint`` (each lowering is a fresh
    engine's fresh trace): a change to those cannot move its StableHLO."""
    before = _lower_engine(which).as_text()

    def boom(*a, **kw):
        raise AssertionError("an engine program reached the remat path")

    monkeypatch.setattr(T, "_attention_flash", boom)
    monkeypatch.setattr(T, "resolve_remat_policy", boom)
    monkeypatch.setattr(jax, "checkpoint", boom)
    assert _lower_engine(which).as_text() == before


# ------------------------------------------------------------- host spans

def test_maybe_span_without_a_stream_annotates_and_writes_nothing(
        tmp_path, monkeypatch):
    opened = []

    class Recorder(contextlib.nullcontext):
        def __init__(self, name, **kw):
            super().__init__()
            opened.append((name, kw))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    monkeypatch.chdir(tmp_path)
    with maybe_span(None, "serve/admit", round=3, replica=None):
        pass
    assert opened == [("serve/admit", {"round": 3})]   # None left out
    assert list(tmp_path.iterdir()) == []


def _host_events(trace_dir) -> dict[str, list]:
    from jax.profiler import ProfileData
    files = list(trace_dir.rglob("*.xplane.pb"))
    assert files
    out: dict[str, list] = {}
    for plane in ProfileData.from_file(str(files[0])).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    out.setdefault(e.name, []).append(
                        dict(e.stats, _start=e.start_ns,
                             _end=e.start_ns + e.duration_ns))
    return out


def _inside(spans: list, outer: list) -> list:
    """Those of ``spans`` that lie inside one of ``outer``."""
    return [s for s in spans if any(o["_start"] <= s["_start"]
                                    and s["_end"] <= o["_end"] for o in outer)]


def test_a_bare_profiler_trace_holds_the_program_spans(tmp_path):
    """No ``TelemetryRun``: ``serve/*``, ``pump/*`` and ``prefetch/*`` land
    in a plain ``jax.profiler`` trace, with their attributes.  A plain
    serving round opens no ``pump/`` span (its burst is one read, the
    engine's own); the pump's two come from a loop of its own, as the
    training drivers and the speculative burst drive it."""
    eng = _engine()
    rng = np.random.default_rng(0)
    for n in (5, 7):
        eng.submit(rng.integers(1, 256, size=n).astype(np.int32),
                   max_new_tokens=4)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        eng.run()
        with StepPump(sync_every=2) as pump:
            for loss in (1.0, 2.0, 3.0, 4.0):
                pump.emit(jnp.float32(loss))
        assert pump.losses == [1.0, 2.0, 3.0, 4.0]
        with DevicePrefetcher(iter([np.zeros((2, 4), np.int32)] * 2),
                              transform=jnp.asarray) as pref:
            assert len(list(pref)) == 2
    finally:
        jax.profiler.stop_trace()
    ev = _host_events(tmp_path)
    assert set(ROUND_SPANS) <= set(ev), sorted(set(ROUND_SPANS) - set(ev))
    assert {"pump/sync_every", "pump/resolve", "prefetch/stage",
            "prefetch/wait"} <= set(ev)
    # a sync point resolves the losses retired since the last, one read each
    assert [s["reads"] for s in ev["pump/resolve"]] == [2, 2]
    assert {s["arrays"] for s in ev["serve/burst_sync"]} == {1}
    pumped = [s for name in ev if name.startswith("pump/") for s in ev[name]]
    assert pumped and not _inside(pumped, ev["serve/round"])
    assert len(_inside(ev["serve/burst_sync"], ev["serve/round"])) \
        == len(ev["serve/burst_sync"]) > 0
    assert len(ev["serve/round"]) == eng.stats["rounds"]
    assert sorted(s["round"] for s in ev["serve/round"]) \
        == list(range(eng.stats["rounds"]))
    assert {s["rid"] for s in ev["serve/prefill_sync"]} == {0, 1}


def test_a_round_emits_the_span_set_in_order_nested_in_the_round(tmp_path):
    t = TelemetryRun("serving", config={"num_steps": 0},
                     results_dir=str(tmp_path), run_name="spans")
    with t as telem:
        eng = _engine(telem=telem)
        eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=3)
        eng.run()
        telem.finalize()
    spans = [s for s in read_spans(t.run_dir) if s["cat"] == "serve"]
    rounds = [s for s in spans if s["name"] == "serve/round"]
    assert [s["round"] for s in rounds] == list(range(len(rounds))) \
        and len(rounds) == eng.stats["rounds"]
    first = sorted((s for s in spans if s["round"] == 0),
                   key=lambda s: (s["ts_us"], -s["dur_us"]))
    assert [s["name"] for s in first] == ROUND_SPANS
    lo, hi = first[0]["ts_us"], first[0]["ts_us"] + first[0]["dur_us"]
    for s in first[1:]:
        assert lo <= s["ts_us"] and s["ts_us"] + s["dur_us"] <= hi + 1e-3
    # one request is concerned: its rid rides along
    assert all(s["rid"] == 0 for s in first[2:7])
    stamped = [s for s in spans if "t_first_s" in s]
    assert len(stamped) == 1 and stamped[0]["name"] == "serve/bookkeep"


def test_engine_span_names_are_static_with_no_pragma():
    import inspect

    from distributed_training_sandbox_tpu.serving import engine
    src = inspect.getsource(engine)
    assert "span-ok" not in src
    assert [f for f in lint_source(src, "engine.py")
            if f.check == "span-name-not-static"] == []
    names = set(re.findall(r'maybe_span\([^,]+,\s*"([^"]+)"', src))
    assert names == set(ROUND_SPANS)


def test_queue_wait_and_admitted_add_up():
    eng = _engine()
    rng = np.random.default_rng(1)
    reqs = [eng.submit(rng.integers(1, 256, size=6).astype(np.int32),
                       max_new_tokens=3, arrival_s=a)
            for a in (0.0, 0.0, 0.0)]          # two slots: the third waits
    eng.run()
    assert eng.stats["admitted"] == 3
    waits = [r.t_admit - r.t_submit for r in reqs]
    assert eng.stats["queue_wait_s"] == pytest.approx(sum(waits))
    assert max(waits) > 0 and min(waits) >= 0


# ------------------------------------- the crossings of the host-device boundary

def _served(cfg=TINY, lengths=(5, 7, 13, 3), max_new=4, **kw):
    """An engine that has served ``lengths`` (13 tokens: two chunks of 8),
    with its spans when a telemetry run is wired through ``kw``."""
    eng = _engine(cfg, **kw)
    rng = np.random.default_rng(0)
    for n in lengths:
        eng.submit(rng.integers(1, 256, size=n).astype(np.int32),
                   max_new_tokens=max_new)
    eng.run()
    return eng


@pytest.fixture
def small_scan(monkeypatch):
    from distributed_training_sandbox_tpu.models import gdn_hybrid
    monkeypatch.setattr(gdn_hybrid, "SCAN_CHUNK", 4)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """``served(block)``: ONE engine a block that has served ``_served``'s
    four prompts under a telemetry run, with its ``serve`` spans.  The tests
    that only read what that service left (counters, spans, the programs'
    caches) share it; a test that serves something else builds its own."""
    from distributed_training_sandbox_tpu.models import gdn_hybrid
    made = {}

    def get(block: str):
        if block not in made:
            t = TelemetryRun("serving", config={"num_steps": 0},
                             results_dir=str(tmp_path_factory.mktemp(block)),
                             run_name="crossings")
            with pytest.MonkeyPatch.context() as m, t as telem:
                m.setattr(gdn_hybrid, "SCAN_CHUNK", 4)
                eng = _served({"dense": TINY, "hybrid": HYBRID}[block],
                              telem=telem)
                telem.finalize()
            made[block] = eng, [s for s in read_spans(t.run_dir)
                                if s["cat"] == "serve"]
        return made[block]

    return get


@pytest.mark.parametrize("block", ["dense", "hybrid"])
def test_crossings_are_counted_where_they_happen(block, served):
    """Launches, reads and puts are what the round structure implies: one
    launch a decode step and a prefill chunk; a burst's sync point reads
    ONE array (the carry: the device's counters and a token row a step), a
    finished prompt one; a burst ships five mirrors and a chunk four
    arrays, five where the prefill program takes the batch slot."""
    eng, _ = served(block)
    s, counters = eng.stats, bool(eng._device_counters)
    assert counters == (block == "hybrid")
    bursts, rem = divmod(s["decode_steps"], eng.sync_every)
    assert rem == 0 and bursts > 0 and s["prefill_chunks"] == 5
    assert s["launches"] == s["decode_steps"] + s["prefill_chunks"]
    assert s["d2h_reads"] == bursts + 4
    per_chunk = 5 if block == "hybrid" else 4
    # the zeros every burst's carry starts from were put once, at
    # construction
    assert s["h2d_puts"] == 5 * bursts + per_chunk * s["prefill_chunks"] + 1
    B, Pn = eng.max_batch, eng.pages_per_request
    carry = 4 * (len(eng._device_counters) + eng.sync_every * B)
    assert eng._carry_zero.nbytes == carry
    assert s["h2d_bytes"] == bursts * (4 * B * 3 + B + 4 * B * Pn) \
        + s["prefill_chunks"] * 4 * (Pn + 8 + per_chunk - 2) + carry
    assert s["d2h_bytes"] == bursts * carry + 4 * 4
    # one a sync POINT and no more: the pump counts none in a plain burst
    assert s["host_sync_count"] == bursts + 4


def _parent_burst(self, t0):
    """A decode burst as it crossed the boundary before the carry held the
    token rows: every step's row an array of its own and the device's
    counters a chain beside them, each read by itself.  The chain is the
    engine's own carry (the counters lead it; the rows it also collects
    are not read), so both crossings run ONE decode executable."""
    L0, A0 = self._h_lengths.copy(), self._h_active.copy()
    toks_d, len_d, stop_d, act_d, pages_d = self._stage_burst()
    bufs = self.pool.bufs
    counted = self._carry_zero
    rows = []
    for _ in range(self.sync_every):
        toks_d, len_d, act_d, bufs, _occ, counted = self._decode(
            bufs, self._params, pages_d, toks_d, len_d, stop_d, act_d,
            counted)
        rows.append(np.asarray(toks_d))
    self.pool.bufs = bufs
    self.stats["decode_steps"] += self.sync_every
    for name, count in zip(self._device_counters, np.asarray(counted)):
        self.stats[name] += int(count)
    active, lengths = A0.copy(), L0.copy()
    for row in rows:
        for b in np.nonzero(active)[0]:
            self.batcher.slot_request(int(b)).tokens.append(int(row[b]))
        lengths = lengths + active
        active = active & (lengths < self._h_stop)
    self._h_tokens = rows[-1].copy()
    self._retire_burst(active, lengths, t0)


@pytest.mark.parametrize("sync_every", [1, 2, 8])
@pytest.mark.parametrize("block", BLOCKS)
def test_a_plain_burst_is_one_read_of_one_array(block, sync_every, small_scan,
                                                monkeypatch):
    """Whatever the block and the burst's length: ``serve/burst_sync``
    holds exactly one ``_read`` of one array, no ``pump/`` span opens (a
    plain burst hands the pump nothing), and tokens and device counters
    are what the parent's crossing, a read a step, gives."""
    _, cfg, params = _block(block)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)
               for n in (5, 19, 7)]
    # both crossings run the same two programs: one engine serves the
    # prompts twice, and a service is read as the counters' rise over it
    eng = ServingEngine(params, cfg, max_batch=2, page_size=8,
                        max_seq_len=32, prefill_chunk=16,
                        sync_every=sync_every)

    def serve(**patch):
        before = dict(eng.stats)
        for name, fn in patch.items():
            setattr(eng, name, fn.__get__(eng))
        reqs = [eng.submit(p, max_new_tokens=new)
                for p, new in zip(prompts, (6, 3, 11))]
        eng.run()
        for name in patch:
            delattr(eng, name)      # the class's own method again
        return ({k: v - before[k] for k, v in eng.stats.items()
                 if isinstance(v, int)}, [r.tokens for r in reqs])

    want, want_tokens = serve(_decode_burst=_parent_burst)

    stack, opened, reads = [], [], []

    class Recorder:
        def __init__(self, name, **kw):
            self.name = name
            opened.append((name, kw))

        def __enter__(self):
            stack.append(self.name)
            return self

        def __exit__(self, *exc):
            stack.pop()

        def set_metadata(self, **kw):   # what a span learns inside itself
            pass

    def counting_read(self, arrs):
        reads.append((stack[-1], [a.shape for a in arrs]))
        return ServingEngine._read(self, arrs)

    with monkeypatch.context() as m:
        m.setattr(jax.profiler, "TraceAnnotation", Recorder)
        stats, tokens = serve(_read=counting_read)
    bursts = stats["decode_steps"] // sync_every
    n = len(eng._device_counters)
    assert (n > 0) == (block != "dense_gqa")
    assert [r for r in reads if r[0] == "serve/burst_sync"] \
        == [("serve/burst_sync", [(n + sync_every * eng.max_batch,)])] * bursts
    assert len(reads) == bursts + len(prompts) == stats["d2h_reads"]
    syncs = [kw for name, kw in opened if name == "serve/burst_sync"]
    assert len(syncs) == bursts > 0 and {kw["arrays"] for kw in syncs} == {1}
    assert not [name for name, _ in opened if name.startswith("pump/")]
    assert stats["host_sync_count"] == bursts + len(prompts)
    # against the parent's crossing
    assert tokens == want_tokens
    assert stats["decode_steps"] == want["decode_steps"]
    for name in eng._device_counters:
        assert stats[name] == want[name] > 0, name


def test_a_speculative_burst_launches_draft_verify_and_accept():
    k = 2
    eng = _served(lengths=(5, 7), spec_k=k, draft_layers=1)
    s = eng.stats
    assert s["launches"] == 2 * s["prefill_chunks"] \
        + s["decode_steps"] * (k + 2)
    bursts = s["decode_steps"] // eng.sync_every
    assert s["d2h_reads"] == bursts * (2 * eng.sync_every + 1) + 2
    # its verify steps still go through the pump, which counts its own
    assert s["host_sync_count"] == 2 * bursts + 2


@pytest.mark.parametrize("block", ["dense", "hybrid"])
def test_spans_carry_what_crossed_and_every_launch_is_in_a_dispatch(
        block, served):
    eng, spans = served(block)
    by = lambda name: [s for s in spans if s["name"] == name]  # noqa: E731
    stats = eng.stats
    # puts and reads: the spans' attributes add up to the counters
    stages = by("serve/burst_stage") + by("serve/prefill_stage")
    syncs = by("serve/burst_sync") + by("serve/prefill_sync")
    # but for the construction's one put, the zeros of the carry
    assert sum(s["arrays"] for s in stages) == stats["h2d_puts"] - 1
    assert sum(s["bytes"] for s in stages) == stats["h2d_bytes"] \
        - eng._carry_zero.nbytes
    assert sum(s["arrays"] for s in syncs) == stats["d2h_reads"]
    assert sum(s["bytes"] for s in syncs) == stats["d2h_bytes"]
    assert {s["arrays"] for s in by("serve/burst_sync")} == {1}
    # the work a launch carries
    assert sum(s["rows"] for s in by("serve/prefill_dispatch")) \
        == 5 + 7 + 13 + 3
    assert all(1 <= s["live"] <= eng.max_batch
               for s in by("serve/burst_dispatch"))
    # launches: one span each, inside a dispatch span of its own round
    launches = by("serve/launch_dispatch")
    assert len(launches) == stats["launches"]
    outer = by("serve/burst_dispatch") + by("serve/prefill_dispatch")
    for s in launches:
        assert s["program"] in ("decode", "prefill") and s["k"] >= 0
        lo, hi = s["ts_us"], s["ts_us"] + s["dur_us"]
        assert [o["name"] for o in outer
                if o["round"] == s["round"] and o["ts_us"] <= lo
                and hi <= o["ts_us"] + o["dur_us"] + 1e-3] == [
            "serve/burst_dispatch" if s["program"] == "decode"
            else "serve/prefill_dispatch"], s
    for rnd in {s["round"] for s in launches}:
        ks = [s["k"] for s in launches
              if s["round"] == rnd and s["program"] == "decode"]
        assert ks in ([], list(range(eng.sync_every)))
    # a prompt of two chunks in one round: k counts the round's chunks
    assert {s["k"] for s in launches if s["program"] == "prefill"} == {0, 1}
    # attributes nobody read are gone
    assert not any("steps" in s or "n_prompt" in s for s in spans)


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_the_launch_helper_leaves_the_programs_as_they_were(which, served):
    """No jitted function changed: after rounds served through
    ``_launch`` each program has the one executable it warmed up with,
    and lowers to the text a fresh engine's does."""
    eng, _ = served("dense")
    assert eng.retraces_after_warmup() == 0
    assert {"decode": eng._decode, "prefill": eng._prefill}[
        which]._cache_size() == 1
    B, Pn = eng.max_batch, eng.pages_per_request
    z = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
    args = (eng._decode, eng.pool.bufs, eng._params, z(B, Pn), z(B), z(B),
            z(B), jnp.zeros((B,), bool), eng._carry_zero) \
        if which == "decode" else (
        eng._prefill, eng.pool.bufs, eng._params_pre, z(1, Pn), z(1, 8),
        jnp.int32(0), jnp.int32(5))
    assert args[0].lower(*args[1:]).as_text() \
        == _lower_engine(which).as_text()


def test_the_report_carries_the_crossings(served):
    eng, _ = served("dense")
    crossings = eng.slo_report()["scheduler"]["crossings"]
    assert crossings == {k: eng.stats[k] for k in (
        "launches", "h2d_puts", "h2d_bytes", "d2h_reads", "d2h_bytes")}
    assert crossings["launches"] and crossings["d2h_reads"]
