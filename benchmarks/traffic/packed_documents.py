"""Training traffic: an endless seeded stream of heavy-tailed documents,
packed into fixed windows by the PROGRAM's own ``pack_tokens`` /
``packed_batches``.

Parameters (``workloads/<traffic>.json`` ``params``):

  ``seq_len``, ``global_batch``   the step's shape: ``global_batch``
                                  windows of ``seq_len`` tokens
  ``doc_len``                     a ``_dist.draw_lengths`` spec
  ``block_windows``               windows drawn per refill (default 64)

Token ids are uniform over ``[1, vocab)``; id 0 ends each document, so
document boundaries fall inside windows as they do in a packed corpus.
Nothing here probes the network or reads a file: the stream is a function
of ``(params, seed, vocab)`` alone.
"""

from __future__ import annotations

import hashlib
from typing import Iterator

import numpy as np

from benchmarks.traffic._dist import draw_lengths

DOC_END = 0


def token_blocks(params: dict, seed: int, vocab: int) -> Iterator[np.ndarray]:
    """Endless stream of token blocks, each a whole number of documents
    totalling at least ``block_windows`` windows."""
    rng = np.random.default_rng([int(seed), 0x7061636B])
    need = int(params.get("block_windows", 64)) * (int(params["seq_len"]) + 1)
    mean = max(float(params["doc_len"].get("median", 1)), 1.0)
    while True:
        docs, have = [], 0
        while have < need:
            lens = draw_lengths(rng, params["doc_len"],
                                max(int((need - have) / mean), 1))
            for n in lens:
                body = rng.integers(1, vocab, size=int(n) - 1, dtype=np.int64)
                docs.append(np.append(body, DOC_END))
                have += int(n)
        yield np.concatenate(docs).astype(np.int32)


def batches(params: dict, seed: int, vocab: int):
    """Endless ``(input_ids, labels)`` batches of shape
    ``(global_batch, seq_len)``.  The ragged tail of each block is carried
    into the next, so no token is dropped between blocks."""
    from distributed_training_sandbox_tpu.data import (
        pack_tokens, packed_batches)
    seq_len, gb = int(params["seq_len"]), int(params["global_batch"])
    window = seq_len + 1
    carry = np.zeros(0, np.int32)
    for block in token_blocks(params, seed, vocab):
        stream = np.concatenate([carry, block])
        whole = (len(stream) // (window * gb)) * (window * gb)
        if not whole:
            carry = stream
            continue
        carry = stream[whole:]
        ids, labels = pack_tokens(stream[:whole], seq_len)
        yield from packed_batches(ids, labels, gb)


def digest(params: dict, seed: int, vocab: int, n_batches: int) -> str:
    """sha256 over the first ``n_batches`` batches: the pin for "same seed,
    same stream"."""
    h = hashlib.sha256()
    for i, (ids, labels) in enumerate(batches(params, seed, vocab)):
        if i >= n_batches:
            break
        h.update(np.ascontiguousarray(ids, np.int32).tobytes())
        h.update(np.ascontiguousarray(labels, np.int32).tobytes())
    return h.hexdigest()
