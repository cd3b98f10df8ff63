"""A plain decode burst brings its results back in one array (the carry
``_decode_core`` chains through the burst's steps), and the host replays
the burst from its rows: the tokens a request receives do not depend on
``sync_every``, for any of the five blocks, and are the plain float32
reference's greedy tokens."""

import numpy as np
import pytest

from distributed_training_sandbox_tpu.models import gdn_hybrid as G
from distributed_training_sandbox_tpu.serving import ServingEngine
from tests.serving_blocks import BLOCKS, make, reference_tokens

# (prompt length, new tokens): two slots serve five requests, so a slot is
# granted again in a later round; the first token is the prefill's, so a
# request of n new tokens retires after n - 1 decode steps: 5, 2, 10, 1, 4
# of them fall inside a burst of 2, 4 or 8 steps, whose later rows hold the
# frozen slot's last token and are not the request's
REQUESTS = ((5, 6), (19, 3), (7, 11), (12, 2), (9, 5))


@pytest.fixture(autouse=True)
def small_sub_chunks(monkeypatch):
    monkeypatch.setattr(G, "SCAN_CHUNK", 4)


@pytest.fixture(scope="module")
def models():
    return {block: make(block) for block in BLOCKS}


@pytest.mark.parametrize("sync_every", [1, 2, 4, 8])
@pytest.mark.parametrize("block", BLOCKS)
def test_tokens_are_the_references_whatever_the_burst_length(
        models, block, sync_every):
    fields, cfg, params = models[block]
    eng = ServingEngine(params, cfg, max_batch=2, page_size=8,
                        max_seq_len=32, prefill_chunk=16,
                        sync_every=sync_every)
    rng = np.random.default_rng(3)
    reqs = [eng.submit(rng.integers(1, cfg.vocab_size, size=n)
                       .astype(np.int32), max_new_tokens=new)
            for n, new in REQUESTS]
    eng.run()
    s = eng.stats
    assert s["admitted"] == len(REQUESTS) > eng.max_batch
    assert s["decode_steps"] % sync_every == 0
    if sync_every > 1:      # somebody reached stop_at inside a burst
        assert any((new - 1) % sync_every for _, new in REQUESTS)
    for req, (_, new) in zip(reqs, REQUESTS):
        assert len(req.tokens) == new
        assert req.tokens == reference_tokens(block, fields, params,
                                              req.prompt, req.tokens), req.rid
    # one blocking read a burst, one a finished prompt
    assert s["d2h_reads"] == s["decode_steps"] // sync_every + len(REQUESTS)
    assert eng.retraces_after_warmup() == 0
