"""The traffic generators are seeded and pinned: the same seed gives a
byte-identical stream, another seed another one.  CPU only."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.traffic import packed_documents as PD  # noqa: E402
from benchmarks.traffic import request_stream as RS  # noqa: E402
from benchmarks.traffic._dist import draw_lengths  # noqa: E402


def _traffic(name):
    return json.loads((ROOT / f"benchmarks/workloads/{name}.json").read_text())


SMALL_DOCS = {"seq_len": 64, "global_batch": 2, "block_windows": 4,
              "doc_len": {"dist": "lognormal", "median": 20, "sigma": 1.0,
                          "min": 4, "max": 200}}


def test_packed_documents_same_seed_same_bytes():
    assert PD.digest(SMALL_DOCS, 7, 512, 9) == PD.digest(SMALL_DOCS, 7, 512, 9)
    assert PD.digest(SMALL_DOCS, 7, 512, 9) != PD.digest(SMALL_DOCS, 8, 512, 9)


def test_packed_documents_digest_is_pinned():
    assert PD.digest(SMALL_DOCS, 7, 512, 9) == PINNED["docs"]


def test_packed_batches_are_windows_with_shifted_labels():
    it = PD.batches(SMALL_DOCS, 3, 512)
    seen = []
    for _ in range(7):                     # crosses a block refill
        ids, labels = next(it)
        assert ids.shape == labels.shape == (2, 64)
        assert ids.dtype == labels.dtype == np.int32
        assert (ids[:, 1:] == labels[:, :-1]).all()
        assert ids.min() >= 0 and ids.max() < 512
        seen.append(ids)
    # documents end inside windows
    assert any((b == PD.DOC_END).any() for b in seen)


@pytest.mark.parametrize("name,shape", [("packed-docs-8k", (4, 8192)),
                                        ("packed-docs-32k", (1, 32768)),
                                        ("packed-docs-2k", (16, 2048))])
def test_the_cells_training_stream_has_the_cells_shape(name, shape):
    """Every training mix is the same 32,768 tokens a step of the same
    documents; only the window's length differs."""
    t = _traffic(name)
    ids, labels = next(PD.batches(t["params"], 1, 128256))
    assert ids.shape == labels.shape == shape
    assert ids.size == 32768 and ids.max() < 128256
    assert t["params"]["doc_len"] == _traffic("packed-docs-8k")["params"][
        "doc_len"]


@pytest.mark.parametrize("name", ["chat-poisson", "doc-backlog"])
def test_request_stream_same_seed_same_bytes(name):
    p = _traffic(name)["params"]
    a = RS.generate(p, 11, 128256, 5.0)
    b = RS.generate(p, 11, 128256, 5.0)
    c = RS.generate(p, 12, 128256, 5.0)
    assert RS.digest(a) == RS.digest(b) != RS.digest(c)


def test_request_stream_digest_is_pinned():
    p = _traffic("chat-poisson")["rehearse"]["params"]
    assert RS.digest(RS.generate(p, 5, 512, 10.0)) == PINNED["requests"]


def test_chat_lengths_and_arrivals_are_as_the_file_says():
    t = _traffic("chat-poisson")
    p = {**t["params"], "arrival": {"process": "poisson", "rate_per_s": 50.0}}
    tr = RS.generate(p, 1, 128256, 20.0)
    # the cell's own arrivals: Poisson conditioned on its count, so the
    # gaps are exponential (pile-ups and lulls included), not a grid
    rate = t["params"]["arrival"]["rate_per_s"]
    own = np.array([r.due_s for r in RS.generate(t["params"], 1, 128256,
                                                 30.0)])
    assert len(own) == round(rate * 30.0)
    gaps = np.diff(own) * rate           # in units of the mean gap
    assert gaps.min() < 0.1 and gaps.max() > 3.0
    assert 0.7 <= gaps.std() <= 1.3      # an exponential's is 1, a grid's 0.4
    assert "stratified" not in t["params"]["arrival"]
    due = np.array([r.due_s for r in tr])
    plen = np.array([len(r.prompt) for r in tr])
    olen = np.array([r.max_new for r in tr])
    assert len(tr) == 1000        # 50/s for 20 s, conditioned on its count
    assert (np.diff(due) >= 0).all() and due.max() < 20.0
    assert plen.min() >= 32 and plen.max() <= 1024
    assert olen.min() >= 16 and olen.max() <= 256
    assert 250 <= np.median(plen) <= 262 and 93 <= np.median(olen) <= 99
    # stratified: another seed reorders the same lengths
    other = RS.generate(p, 2, 128256, 20.0)
    assert sorted(len(r.prompt) for r in other) == sorted(plen)
    assert [len(r.prompt) for r in other] != list(plen)
    assert (plen + olen <= t["engine"]["max_seq_len"]).all()
    assert all(r.prompt.min() >= 1 and r.prompt.max() < 128256 for r in tr)


def test_backlog_is_all_due_at_zero_and_fits_the_engine():
    t = _traffic("doc-backlog")
    tr = RS.generate(t["params"], 1, 128256, 30.0)
    assert len(tr) == t["params"]["arrival"]["count"]
    assert all(r.due_s == 0.0 for r in tr)
    assert all(2048 <= len(r.prompt) <= 6144 and 32 <= r.max_new <= 128
               for r in tr)
    assert max(len(r.prompt) + r.max_new for r in tr) \
        <= t["engine"]["max_seq_len"]


@pytest.mark.parametrize("spec,lo,hi", [
    ({"dist": "fixed", "value": 7}, 7, 7),
    ({"dist": "uniform", "min": 3, "max": 5}, 3, 5),
    ({"dist": "lognormal", "median": 100, "sigma": 2.0, "min": 10,
      "max": 300}, 10, 300)])
def test_draw_lengths_stay_in_range(spec, lo, hi):
    x = draw_lengths(np.random.default_rng(0), spec, 2000)
    assert x.min() >= lo and x.max() <= hi
    if lo != hi:
        assert x.min() == lo and x.max() == hi     # the clip bites both ends


def test_stratified_blocks_each_hold_the_whole_distribution():
    spec = {"dist": "uniform", "min": 2048, "max": 6144, "stratified": 8}
    x = draw_lengths(np.random.default_rng(3), spec, 20)
    assert sorted(x[:8]) == sorted(x[8:16]) == [
        2304, 2816, 3328, 3840, 4352, 4864, 5376, 5888]
    assert len(x) == 20 and len(draw_lengths(
        np.random.default_rng(3), spec, 0)) == 0


def test_unknown_distribution_or_process_is_an_error():
    with pytest.raises(ValueError):
        draw_lengths(np.random.default_rng(0), {"dist": "zipf", "min": 1,
                                                "max": 2}, 1)
    with pytest.raises(ValueError):
        RS.arrivals(np.random.default_rng(0), {"process": "bursty"}, 1.0)


PINNED = {
    "docs": "2a0dd639e29031a352fd2f2acc939a37f696576a56b6c1fad5c0a12493a03fd2",
    "requests": "a7dd3cd22eb985ff532d38131618b1cb437635b99da4077808454b2d1b3581db",
}
