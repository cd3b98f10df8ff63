"""A prefill chunk runs the head (final norm, whole-vocabulary product,
argmax) only when it ends a prompt (``engine._first_token``): the prefill
programs of all six blocks hold ONE ``cond`` with the vocabulary-wide
product in its taken branch alone, the decode program holds none, a
prompt's first token and its pool are bitwise what a head on every chunk
leaves, and the engine counts the chunks whose head ran."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_sandbox_tpu.models import gdn_hybrid as G
from distributed_training_sandbox_tpu.models import ssm_moe as S
from distributed_training_sandbox_tpu.models import transformer as T
from distributed_training_sandbox_tpu.models.generate import generate
from distributed_training_sandbox_tpu.serving import PagedKVPool, ServingEngine
from distributed_training_sandbox_tpu.serving import engine as E
from distributed_training_sandbox_tpu.serving.kv_pool import ring_pages
from tests.serving_blocks import BLOCKS, make, reference_tokens

pytestmark = pytest.mark.serving

CHUNK = PAGE = 8
MAX_SEQ = 32
PAGES = MAX_SEQ // PAGE
# (prompt length, new tokens) at chunks of 8: three chunks ending inside
# the last, an exact multiple of the chunk, one whole chunk, less than one,
# four chunks; two slots serve the five, so slots are granted again
REQUESTS = ((19, 4), (24, 3), (8, 3), (5, 4), (27, 2))
CHUNKS = sum(-(-n // CHUNK) for n, _ in REQUESTS)


@pytest.fixture(autouse=True)
def small_scan_blocks(monkeypatch):
    monkeypatch.setattr(G, "SCAN_CHUNK", 4)
    monkeypatch.setattr(S, "SCAN_BLOCK", 4)


@pytest.fixture(scope="module")
def models():
    return {block: make(block) for block in BLOCKS}


@pytest.fixture(scope="module")
def tiny():
    """``T.TINY_LM`` with weights scaled like the blocks': what the options
    only the dense block takes are tested on, against ``generate``."""
    cfg = T.TINY_LM
    return cfg, jax.tree.map(lambda x: (x * 3.0).astype(x.dtype),
                             T.init_params(jax.random.PRNGKey(0), cfg))


def _assert_generates(eng, cfg, params, reqs, new):
    for r in reqs:
        ref = np.asarray(generate(params, r.prompt[None], cfg,
                                  max_new_tokens=new,
                                  cache_capacity=eng.view_capacity))[0]
        assert np.asarray(r.tokens, np.int32).tolist() == ref.tolist()


def _prompt(cfg, n, seed):
    return np.random.default_rng(seed).integers(
        1, cfg.vocab_size, size=n).astype(np.int32)


@pytest.fixture(scope="module")
def served(models):
    """block -> (engine, requests) after REQUESTS ran through it, built
    when a test first asks for the block."""
    done = {}

    def get(block):
        if block not in done:
            _, cfg, params = models[block]
            eng = ServingEngine(params, cfg, max_batch=2, page_size=PAGE,
                                max_seq_len=MAX_SEQ, prefill_chunk=CHUNK,
                                sync_every=2)
            reqs = [eng.submit(_prompt(cfg, n, 41 + i), max_new_tokens=new)
                    for i, (n, new) in enumerate(REQUESTS)]
            eng.run()
            done[block] = eng, reqs
        return done[block]

    return get


# ---- (a) the programs: one cond in prefill, none in decode ----------------

def _eqns(jaxpr, inside_cond=False):
    """Every equation of ``jaxpr`` at any depth, with whether it sits in a
    ``cond``'s branch."""
    for eqn in jaxpr.eqns:
        yield eqn, inside_cond
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub, inside_cond or eqn.primitive.name == "cond")


def _is_vocab_wide(eqn, vocab: int) -> bool:
    return eqn.primitive.name == "dot_general" and any(
        vocab in v.aval.shape for v in (*eqn.invars, *eqn.outvars))


def _pool(cfg):
    """The buffers of a pool that holds two requests of ``cfg``'s block."""
    kw = {"n_slots": 2} if cfg.state_slots else {}
    if cfg.swa_moe:
        kw = {"n_pages_window": 2 * ring_pages(cfg, PAGE, CHUNK) + 1}
    return PagedKVPool(cfg, 2 * PAGES + 1, PAGE, **kw).bufs


def _shapes(cfg, rows: int):
    """``(bufs, tables, slot)`` as shapes: that pool, the page tables of
    ``rows`` requests, and the batch slot a block with state carries."""
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    tables = i32(rows, PAGES)
    if cfg.swa_moe:
        tables = (tables, i32(rows, ring_pages(cfg, PAGE, CHUNK)))
    return (jax.eval_shape(lambda: _pool(cfg)), tables,
            (i32(),) if cfg.state_slots else ())


def _prefill_jaxpr(cfg, params, *, batch: int = 0):
    """The single-request prefill program's jaxpr, or with ``batch`` rows
    the batched one's."""
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    bufs, tables, slot = _shapes(cfg, batch or 1)
    if batch:
        return jax.make_jaxpr(partial(E._prefill_batch_core, cfg=cfg))(
            bufs, params, tables, i32(batch, CHUNK), i32(batch),
            i32(batch)).jaxpr
    return jax.make_jaxpr(partial(E._prefill_core, cfg=cfg))(
        bufs, params, tables, i32(1, CHUNK), i32(), i32(), *slot).jaxpr


@pytest.mark.parametrize("block,batch", [(b, 0) for b in BLOCKS]
                         + [("dense_gqa", 3)],
                         ids=[*BLOCKS, "dense_gqa-batched"])
def test_the_prefill_program_holds_the_head_inside_one_cond(
        models, block, batch):
    _, cfg, params = models[block]
    eqns = list(_eqns(_prefill_jaxpr(cfg, params, batch=batch)))
    conds = [e for e, _ in eqns if e.primitive.name == "cond"]
    assert len(conds) == 1
    wide = [inside for e, inside in eqns
            if _is_vocab_wide(e, cfg.vocab_size)]
    assert wide == [True]           # one product, and inside the cond
    skipped, taken = conds[0].params["branches"]
    assert [_is_vocab_wide(e, cfg.vocab_size)
            for e, _ in _eqns(taken.jaxpr)].count(True) == 1
    # the other branch: a constant of the token's shape, no input read
    assert not any(e.primitive.name == "dot_general"
                   for e, _ in _eqns(skipped.jaxpr))
    assert not set(skipped.jaxpr.outvars) & set(skipped.jaxpr.invars)
    (out,) = conds[0].outvars
    assert out.aval.shape == (batch or 1,) and out.aval.dtype == jnp.int32


@pytest.mark.parametrize("block", BLOCKS)
def test_the_decode_program_holds_no_cond(models, block):
    """Every decode row needs its token: the head stays unconditional."""
    _, cfg, params = models[block]
    sd = jax.ShapeDtypeStruct
    i32 = lambda *s: sd(s, jnp.int32)  # noqa: E731
    bufs, tables, _ = _shapes(cfg, 2)
    jaxpr = jax.make_jaxpr(partial(E._decode_core, cfg=cfg))(
        bufs, params, tables, i32(2), i32(2), i32(2), sd((2,), jnp.bool_),
        i32(len(E.device_counters(cfg)) + 2 * 2)).jaxpr
    eqns = list(_eqns(jaxpr))
    assert not [e for e, _ in eqns if e.primitive.name == "cond"]
    assert [_is_vocab_wide(e, cfg.vocab_size)
            for e, _ in eqns].count(True) == 1


# ---- (b) bitwise a head on every chunk ------------------------------------

def _head_every_chunk(bufs, params, pages_row, ids, pos, plen, slot=None, *,
                      cfg):
    """What ``_prefill_core`` was before the branch: the same forward, and
    the head on every chunk."""
    Ck = ids.shape[1]
    apos = pos + jnp.arange(Ck, dtype=jnp.int32)[None, :]
    x, bufs, _ = E._paged_forward(params, ids, cfg, bufs, pages_row, apos,
                                  apos < plen, slot=slot)
    last = jnp.clip(plen - 1 - pos, 0, Ck - 1)
    xl = jax.lax.dynamic_slice_in_dim(x, last, 1, axis=1)
    tok = jnp.argmax(E._last_logits(params, xl, cfg), axis=-1)
    return tok.astype(jnp.int32), bufs


def _chunk_ids(prompt, pos):
    """(1, CHUNK) ids of the chunk at ``pos``, padded with zeros as the
    host pads them (all zeros past the prompt's end)."""
    ids = np.zeros((1, CHUNK), np.int32)
    chunk = prompt[pos:pos + CHUNK]
    ids[0, :len(chunk)] = chunk
    return ids


def _tables(cfg, first_page: int = 1):
    """One request's page tables, from ``first_page`` on."""
    full = np.arange(first_page, first_page + PAGES, dtype=np.int32)[None]
    if not cfg.swa_moe:
        return full
    R = ring_pages(cfg, PAGE, CHUNK)
    return full, np.arange(1, 1 + R, dtype=np.int32)[None]


@pytest.mark.parametrize("block", BLOCKS)
def test_a_three_chunk_prompt_is_bitwise_a_head_on_every_chunk(
        models, block):
    fields, cfg, params = models[block]
    prompt = _prompt(cfg, 2 * CHUNK + 3, 7)
    slot = (np.int32(1),) if cfg.state_slots else ()
    steps = {"cond": E.make_serve_prefill_step(cfg),
             "every": jax.jit(partial(_head_every_chunk,
                                      cfg=E._decode_cfg(cfg)))}
    bufs = {name: _pool(cfg) for name in steps}
    for pos in range(0, len(prompt), CHUNK):
        toks = {}
        for name, step in steps.items():
            toks[name], bufs[name] = step(
                bufs[name], params, _tables(cfg), _chunk_ids(prompt, pos),
                np.int32(pos), np.int32(len(prompt)), *slot)
        for a, b in zip(jax.tree.leaves(bufs["cond"]),
                        jax.tree.leaves(bufs["every"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        if pos + CHUNK < len(prompt):
            assert np.asarray(toks["cond"]).tolist() == [0]
    first = np.asarray(toks["cond"]).tolist()
    assert first == np.asarray(toks["every"]).tolist() != [0]
    assert first == reference_tokens(block, fields, params, prompt, first)


# ---- (c) the batched step's predicate --------------------------------------

def test_the_batched_step_runs_the_head_for_a_live_row_that_ends(tiny):
    """Rows 0 and 1 are live, row 2 is a pad row (``plen == 0``, which
    ``pos + C >= plen`` alone would read as ending).  While neither live
    row ends, every token is the constant; in the chunk where row 1 ends
    its token is bitwise the single-row core's."""
    cfg, params = tiny
    a, b = _prompt(cfg, 3 * CHUNK, 1), _prompt(cfg, CHUNK + 5, 2)
    pages = np.zeros((3, PAGES), np.int32)
    pages[0], pages[1] = _tables(cfg)[0], _tables(cfg, 1 + PAGES)[0]
    plen = np.array([len(a), len(b), 0], np.int32)
    batch = E.make_serve_prefill_batch_step(cfg, flash_prefill=False)
    bufs, toks = _pool(cfg), []
    for pos in (0, CHUNK):
        ids = np.concatenate([_chunk_ids(a, pos), _chunk_ids(b, pos),
                              _chunk_ids(a, len(a))])
        tok, bufs = batch(bufs, params, pages, ids,
                          np.array([pos, pos, 0], np.int32), plen)
        toks.append(np.asarray(tok).tolist())
    assert toks[0] == [0, 0, 0]

    single = E.make_serve_prefill_step(cfg)
    alone = _pool(cfg)
    for pos in (0, CHUNK):
        tok, alone = single(alone, params, pages[1:2], _chunk_ids(b, pos),
                            np.int32(pos), np.int32(len(b)))
    want = int(np.asarray(tok)[0])
    assert want != 0 and toks[1][1] == want
    assert want == int(np.asarray(generate(
        params, b[None], cfg, max_new_tokens=1,
        cache_capacity=MAX_SEQ))[0, 0])


# ---- (d) the counter --------------------------------------------------------

@pytest.mark.parametrize("block", BLOCKS)
def test_prompts_of_several_chunks_get_the_references_tokens(
        models, served, block):
    fields, _, params = models[block]
    eng, reqs = served(block)
    for req, (n, new) in zip(reqs, REQUESTS):
        assert req.n_prompt == n and len(req.tokens) == new
        assert req.tokens == reference_tokens(
            block, fields, params, req.prompt, req.tokens), (block, n)


@pytest.mark.parametrize("block", BLOCKS)
def test_head_chunks_are_the_finished_prompts(served, block):
    eng, reqs = served(block)
    s = eng.stats
    assert s["prefill_chunks"] == CHUNKS
    assert s["prefill_head_chunks"] == len(REQUESTS)
    sched = eng.slo_report()["scheduler"]
    assert sched["prefill_chunks"] == CHUNKS
    assert sched["prefill_head_chunks"] == len(REQUESTS)
    assert eng.retraces_after_warmup() == 0


@pytest.mark.parametrize("tail", [5, 9, 15],
                         ids=["one-chunk-left", "two-chunks-left",
                              "whole-chunks-left"])
def test_a_prefill_that_starts_past_a_cached_prefix_is_generates(tiny, tail):
    """The second prompt's prefill starts at ``pos`` 16, behind two cached
    pages: one final chunk, a non-final one before it, or two whole ones."""
    cfg, params = tiny
    eng = ServingEngine(params, cfg, max_batch=2, page_size=PAGE,
                        max_seq_len=48, prefill_chunk=CHUNK,
                        prefix_cache=True)
    head = _prompt(cfg, 17, 3)
    first = eng.submit(np.concatenate([head, _prompt(cfg, 5, 4)]),
                       max_new_tokens=4)
    eng.run()
    before = dict(eng.stats)
    second = eng.submit(np.concatenate([head, _prompt(cfg, tail, 5)]),
                        max_new_tokens=4)
    eng.run()
    assert eng.prefix_cache.hit_pages == 2
    _assert_generates(eng, cfg, params, (first, second), 4)
    # rows 16 .. 17 + tail of the second prompt, in chunks of 8
    assert eng.stats["prefill_chunks"] - before["prefill_chunks"] \
        == -(-(1 + tail) // CHUNK)
    assert eng.stats["prefill_head_chunks"] \
        - before["prefill_head_chunks"] == 1


def test_head_chunks_of_the_batched_step_are_chunks_with_a_finisher(tiny):
    """Three prompts advance together: the chunk in which two of them end
    runs the head once."""
    cfg, params = tiny
    eng = ServingEngine(params, cfg, max_batch=3, page_size=PAGE,
                        max_seq_len=48, prefill_chunk=CHUNK,
                        flash_prefill=True)
    # 2, 2 and 4 chunks, admitted in the same round: the batched chunks 1..4
    # hold finishers {}, {a, b}, {}, {c}
    reqs = [eng.submit(_prompt(cfg, n, 9 + n), max_new_tokens=3)
            for n in (13, 16, 30)]
    eng.run()
    assert eng.stats["prefill_chunks"] == 4
    assert eng.stats["prefill_head_chunks"] == 2
    assert eng.retraces_after_warmup() == 0
    _assert_generates(eng, cfg, params, reqs, 3)


def test_the_speculative_drafts_prefill_skips_the_head_too(tiny):
    """The draft's prefill rides the same core: its program holds the
    branch, and the served tokens are ``generate``'s."""
    cfg, params = tiny
    eng = ServingEngine(params, cfg, max_batch=2, page_size=PAGE,
                        max_seq_len=MAX_SEQ, prefill_chunk=CHUNK,
                        spec_k=2, draft_layers=1)
    reqs = [eng.submit(_prompt(cfg, n, n), max_new_tokens=4)
            for n in (19, 8)]
    eng.run()
    assert eng.stats["prefill_chunks"] == 4
    assert eng.stats["prefill_head_chunks"] == 2
    assert eng.retraces_after_warmup() == 0
    _assert_generates(eng, cfg, params, reqs, 4)
    eqns = list(_eqns(_prefill_jaxpr(eng.draft_cfg, eng._draft_params)))
    assert [e.primitive.name for e, _ in eqns].count("cond") == 1


def test_the_dispatch_span_says_whether_the_head_ran(tiny, tmp_path):
    from distributed_training_sandbox_tpu.telemetry import (TelemetryRun,
                                                            read_spans)
    cfg, params = tiny
    t = TelemetryRun("serving", config={"num_steps": 0},
                     results_dir=str(tmp_path), run_name="head")
    with t as telem:
        eng = ServingEngine(params, cfg, max_batch=2, page_size=PAGE,
                            max_seq_len=MAX_SEQ, prefill_chunk=CHUNK,
                            telem=telem)
        for n in (19, 8):           # three chunks, then one
            eng.submit(np.arange(1, 1 + n, dtype=np.int32),
                       max_new_tokens=2)
        eng.run()
        telem.finalize()
    spans = [s for s in read_spans(t.run_dir)
             if s["name"] == "serve/prefill_dispatch"]
    by_rid = {rid: [s["head"] for s in spans if s["rid"] == rid]
              for rid in (0, 1)}
    assert by_rid == {0: [0, 0, 1], 1: [1]}
    assert sum(s["head"] for s in spans) == eng.stats["prefill_head_chunks"]


# ---- the tensor-parallel step ----------------------------------------------

def test_the_tensor_parallel_prefill_step_matches_the_unsharded_one(tiny):
    """Chunk by chunk through both compiled steps: the constant on the
    chunks that end no prompt, the same first token on the one that does,
    the same rows in the pool."""
    from distributed_training_sandbox_tpu.utils import make_mesh
    cfg, params = tiny
    mesh = make_mesh({"dp": len(jax.devices()) // 2, "tp": 2},
                     register=False)
    kw = dict(max_batch=2, page_size=PAGE, max_seq_len=MAX_SEQ,
              prefill_chunk=CHUNK)
    plain = ServingEngine(params, cfg, **kw)
    sharded = ServingEngine(params, cfg, mesh=mesh, **kw)
    prompt = _prompt(cfg, 3 * CHUNK, 5)
    toks = {}
    for name, eng in (("plain", plain), ("sharded", sharded)):
        toks[name] = []
        for pos in range(0, len(prompt), CHUNK):
            tok, eng.pool.bufs = eng._prefill(
                eng.pool.bufs, eng._params_pre, _tables(cfg),
                prompt[None, pos:pos + CHUNK], np.int32(pos),
                np.int32(len(prompt)))
            toks[name].append(np.asarray(tok).tolist())
    want = int(np.asarray(generate(params, prompt[None], cfg,
                                   max_new_tokens=1,
                                   cache_capacity=plain.view_capacity))[0, 0])
    assert toks["plain"] == toks["sharded"] == [[0], [0], [want]]
    for a, b in zip(jax.tree.leaves(plain.pool.bufs),
                    jax.tree.leaves(sharded.pool.bufs)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
