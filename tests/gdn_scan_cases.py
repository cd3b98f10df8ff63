"""Inputs on which the chunked scan's triangular system is badly behaved,
and what the scan is held to on them: a float64 token-by-token recurrence.
Shared by ``test_gdn_hybrid.py`` and ``test_gdn_moe.py``.

With ``beta = 2`` a repeated unit key has ``1 - beta k.k = -1``: the
recurrence neither grows nor contracts, ``A`` is 2 everywhere below the
diagonal and the entries of ``(I + A)^-1`` stay at 2, while the POWERS of
``A`` reach 1e6 in a 16-row block before they cancel.  Random keys, which
are nearly orthogonal, show none of this."""

import functools
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

#: (a) 64 identical unit keys, beta 2, no decay; (b) the same with alpha
#: 0.999; (c) runs of 16 identical keys, what a run of one repeated token
#: gives layer 0; (d) keys alternating k, -k; (e) random keys and gates
CASES = ("identical", "identical_decay", "runs_of_16", "alternating",
         "random")


def scan_case(name, *, S=64, nk=2, n=2, dk=64, dv=32, beta_max=2.0, seed=0):
    """``q, k`` (1, S, nk, dk), ``v`` (1, S, n, dv), ``g, beta`` (1, S, n)
    and a NON-zero carried state, float32; q unit and v scaled so that the
    outputs are of size ~1 (a repeated key at ``beta_max`` 2 random-walks
    the state)."""
    rng = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(rng.standard_normal((1, S, nk, dk)))
    v = rng.standard_normal((1, S, n, dv)) * (1.0 if name == "random"
                                              else S ** -0.5)
    s0 = 0.5 * rng.standard_normal((1, n, dk, dv))
    beta, g = np.full((1, S, n), beta_max), np.zeros((1, S, n))
    one = unit(rng.standard_normal((1, 1, nk, dk)))
    if name in ("identical", "identical_decay"):
        k = np.broadcast_to(one, (1, S, nk, dk))
        if name == "identical_decay":
            g = np.full((1, S, n), np.log(0.999))
    elif name == "runs_of_16":
        k = np.repeat(unit(rng.standard_normal((1, -(-S // 16), nk, dk))),
                      16, axis=1)[:, :S]
    elif name == "alternating":
        k = one * np.where(np.arange(S) % 2, -1.0, 1.0)[None, :, None, None]
    else:
        k = unit(rng.standard_normal((1, S, nk, dk)))
        beta = rng.uniform(0, beta_max, (1, S, n))
        g = -rng.uniform(0, 0.2, (1, S, n))
    return tuple(jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta, s0))


def recurrence64(q, k, v, g, beta, s0):
    """The recurrence of ``gdn_hybrid``'s module docstring token by token
    in numpy float64, value head ``r`` on key head ``r // (n / n_k)``:
    ``o`` (B, S, n, dv) and the state after the last row."""
    q, k, v, g, beta, s = (np.asarray(a, np.float64)
                           for a in (q, k, v, g, beta, s0))
    per = v.shape[2] // q.shape[2]
    q, k = np.repeat(q, per, axis=2), np.repeat(k, per, axis=2)
    o = np.zeros(v.shape)
    for t in range(v.shape[1]):
        s = np.exp(g[:, t])[..., None, None] * s
        u = beta[:, t][..., None] * (
            v[:, t] - np.einsum("bnkv,bnk->bnv", s, k[:, t]))
        s = s + k[:, t][..., None] * u[..., None, :]
        o[:, t] = np.einsum("bnkv,bnk->bnv", s, q[:, t])
    return o, s


def errors(got, want):
    """Largest absolute error of ``o`` and of the final state."""
    return tuple(float(np.abs(np.asarray(a, np.float64) - w).max())
                 for a, w in zip(got, want))


def assert_as_exact_as_the_solve(G, args):
    """``G.chunked_scan`` at 64-row sub-chunks against the recurrence in
    float64: on ``o`` and on the final state the error is no more than
    twice what the same scan reads with ``(I + A)^-1`` from
    ``solve_triangular``, and never over 1e-3."""
    want = recurrence64(*args)
    with mock.patch.object(G, "SCAN_CHUNK", 64), \
            jax.default_matmul_precision("highest"):
        got = errors(G.chunked_scan(*args), want)
        with mock.patch.object(G, "unit_lower_inverse", solve_inverse):
            solve = errors(G.chunked_scan(*args), want)
    for err, err_solve in zip(got, solve):
        assert err <= max(2 * err_solve, 1e-6) and err <= 1e-3, (got, solve)


def solve_inverse(A):
    """What ``chunked_scan`` did until PR 34: ``(I + A)^-1`` as one
    ``triangular_solve`` against the identity."""
    eye = jnp.eye(A.shape[-1], dtype=A.dtype)
    return jax.scipy.linalg.solve_triangular(
        A + eye, jnp.broadcast_to(eye, A.shape), lower=True,
        unit_diagonal=True)


def neumann_inverse(A, b=16):
    """What PR 33 tried and its review took out: the ``b``-row diagonal
    blocks of ``(I + A)^-1`` as their finite Neumann series, ``(I - L)(I +
    L^2)(I + L^4)..``, merged pairwise as block forward substitution,
    ``[[T1, 0], [-T2 A21 T1, T2]]``; every product in float32."""
    C = A.shape[-1]
    mm = functools.partial(jnp.matmul, precision=lax.Precision.HIGHEST)
    block = lambda i, j, s: A[..., i * s:(i + 1) * s,  # noqa: E731
                              j * s:(j + 1) * s]
    eye = jnp.eye(b, dtype=A.dtype)
    L = jnp.stack([block(p, p, b) for p in range(C // b)], axis=-3)
    T, P, p = eye - L, mm(L, L), 2
    while p < b:
        T, P, p = mm(T, eye + P), mm(P, P), 2 * p
    while T.shape[-3] > 1:
        T1, T2 = T[..., 0::2, :, :], T[..., 1::2, :, :]
        A21 = jnp.stack([block(2 * p + 1, 2 * p, b)
                         for p in range(T1.shape[-3])], axis=-3)
        T = jnp.concatenate([
            jnp.concatenate([T1, jnp.zeros_like(T1)], axis=-1),
            jnp.concatenate([-mm(T2, mm(A21, T1)), T2], axis=-1)], axis=-2)
        b *= 2
    return T[..., 0, :, :]
