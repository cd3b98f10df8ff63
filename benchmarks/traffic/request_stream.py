"""Serving traffic: a seeded trace of requests with due times.

Copied in structure from the program's ``serving/traces.py`` (one seeded
generator, nothing reads a clock, arrivals are seconds from t = 0) and
given real length distributions.  Parameters (``workloads/<traffic>.json``
``params``):

  ``arrival``     ``{"process": "poisson", "rate_per_s": r}``: an open
                  loop of independent users.  The window holds
                  ``round(r x window)`` arrivals at independent uniform
                  times: a Poisson process conditioned on its count, so
                  that every run offers the same number of requests and
                  the seed decides where the bursts and lulls fall (an
                  unconditioned count swings by 10% from seed to seed at
                  100 requests, and the tail of the time to first token
                  with it).  The gaps are exponential, pile-ups included:
                  they are what a scheduler or an admission policy acts
                  on.
                  Or ``{"process": "backlog", "count": n}``: an offline
                  job, ``n`` requests all due at t = 0, more than the
                  window can finish.
  ``prompt_len``  a ``_dist.draw_lengths`` spec
  ``output_len``  a ``_dist.draw_lengths`` spec
  ``max_total``   prompt + output is clipped to this (the engine's
                  ``max_seq_len``) by shortening the prompt

No prefixes are shared: every prompt is drawn afresh, ids uniform over
``[1, vocab)``.
"""

from __future__ import annotations

import hashlib
import struct
from dataclasses import dataclass

import numpy as np

from benchmarks.traffic._dist import draw_lengths


@dataclass(frozen=True)
class TraceRequest:
    due_s: float
    prompt: np.ndarray
    max_new: int


def arrivals(rng, spec: dict, horizon_s: float) -> np.ndarray:
    if spec["process"] == "backlog":
        return np.zeros(int(spec["count"]), np.float64)
    if spec["process"] == "poisson":
        rate = float(spec["rate_per_s"])
        n = int(round(rate * horizon_s))
        return np.sort(rng.uniform(0.0, horizon_s, size=n))
    raise ValueError(f"unknown arrival process {spec['process']!r}")


def generate(params: dict, seed: int, vocab: int,
             horizon_s: float) -> list[TraceRequest]:
    """The trace of one run: every request due in ``[0, horizon_s)``."""
    rng = np.random.default_rng([int(seed), 0x72657173])
    due = arrivals(rng, params["arrival"], float(horizon_s))
    n = len(due)
    plen = draw_lengths(rng, params["prompt_len"], n)
    olen = draw_lengths(rng, params["output_len"], n)
    cap = int(params["max_total"])
    trace = []
    for t, p, o in zip(due, plen, olen):
        p = max(min(int(p), cap - int(o)), 1)
        prompt = rng.integers(1, vocab, size=p, dtype=np.int64)
        trace.append(TraceRequest(float(t), prompt.astype(np.int32), int(o)))
    return trace


def digest(trace: list[TraceRequest]) -> str:
    """sha256 over due times (as IEEE-754 bits), token ids and output
    lengths, as ``serving/traces.py`` ``trace_digest`` does."""
    h = hashlib.sha256()
    for r in trace:
        h.update(struct.pack("<dq", float(r.due_s), int(r.max_new)))
        h.update(np.ascontiguousarray(r.prompt, np.int32).tobytes())
    return h.hexdigest()
