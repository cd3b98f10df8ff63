"""Length distributions the traffic generators share.  Everything draws
from the one ``numpy.random.Generator`` the caller passes, in a fixed
order, so a seed pins the stream."""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

_NORMAL = NormalDist()


def _quantiles(spec: dict, n: int) -> np.ndarray:
    """The distribution's quantiles at (i + 1/2)/n, clipped and rounded."""
    lo, hi = int(spec["min"]), int(spec["max"])
    q = (np.arange(n) + 0.5) / n
    if spec["dist"] == "uniform":
        x = lo + q * (hi + 1 - lo) - 0.5
    elif spec["dist"] == "lognormal":
        z = np.array([_NORMAL.inv_cdf(float(v)) for v in q])
        x = np.exp(math.log(float(spec["median"])) + float(spec["sigma"]) * z)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def draw_lengths(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    """``n`` integer lengths from ``spec``:

    ``{"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}``
    (heavy-tailed, clipped to [a, b]) or
    ``{"dist": "uniform", "min": a, "max": b}`` (inclusive) or
    ``{"dist": "fixed", "value": v}``.

    With ``"stratified": true`` the ``n`` lengths are the distribution's
    quantiles at (i + 1/2)/n, in an order drawn from the seed: every run
    then holds the same amount of work, and the seed decides only which
    request gets which length.  ``"stratified": k`` does so in blocks of
    ``k``, for a stream of which a run consumes only a part."""
    dist = spec["dist"]
    if dist == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    lo, hi = int(spec["min"]), int(spec["max"])
    block = spec.get("stratified")
    if block and n:
        # True: one block of all n; an integer: blocks of that many, so
        # that every stretch of the stream holds the whole distribution
        block = n if block is True else int(block)
        out = [_quantiles(spec, min(block, n - s))[
            rng.permutation(min(block, n - s))] for s in range(0, n, block)]
        return np.concatenate(out) if out else np.zeros(0, np.int64)
    if dist == "uniform":
        return rng.integers(lo, hi + 1, size=n).astype(np.int64)
    if dist == "lognormal":
        x = rng.lognormal(math.log(float(spec["median"])),
                          float(spec["sigma"]), size=n)
        return np.clip(np.rint(x), lo, hi).astype(np.int64)
    raise ValueError(f"unknown length distribution {dist!r}")
