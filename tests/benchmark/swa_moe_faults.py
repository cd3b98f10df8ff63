"""Faults planted in the timed path of the block of sliding-window and full
attention layers over held experts, to show that the comparison which
decides ``correct`` separates them from the sound program: in the rehearsal
(``test_bench_swa_moe.py``) and on the chip::

    python3 tests/benchmark/swa_moe_faults.py <fault> --workload \\
        serve-swa-moe-mixedlen --seed <n> --seconds 8 --probe '{}'

runs ``benchmarks/run.py`` with the fault in place (``--probe`` prints the
check's distances and no result line; without it, with ``--trace 0``, the
run prints the harness's own result line, ``correct`` false).  Every planted
fault touches DECODE steps only (a chunk of more than one row runs the sound
code); the reference is as it is.  ``matmuls_in_int8`` is no planted line
but the program as written, computing in the nearest precision below the one
the configuration states, in both programs.
"""

from __future__ import annotations

import contextlib
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from tests.benchmark import gdn_moe_faults as _older  # noqa: E402
from tests.benchmark.gdn_hybrid_faults import _patched  # noqa: E402
from tests.benchmark.gdn_moe_faults import matmuls_in_int8  # noqa: E402


def _decode_view(window_of):
    """``kv_pool.ring_view`` as the engine calls it, a decode step's window
    replaced by ``window_of(ring entries, page, window)``."""
    from distributed_training_sandbox_tpu.serving import engine as E
    real = E.ring_view

    def faulty(ring, apos, window, page):
        if apos.shape[1] == 1:
            window = window_of(ring.shape[1], page, window)
        return real(ring, apos, window, page)

    return _patched(E, "ring_view", faulty)


def window_ignored():
    """A decode step's window layers read every row their ring still holds,
    ``(ring entries - 1) x page + 1`` of them (4,593 at the cell's sizes
    against the window's 4,096): as far as a layer that kept no more can
    ignore its window."""
    return _decode_view(lambda entries, page, window:
                        (entries - 1) * page + 1)


def window_off_by_a_page():
    """A decode step's window layers see one page of rows past the window
    (4,112 for 4,096)."""
    return _decode_view(lambda entries, page, window: window + page)


def ring_not_wrapped():
    """A decode step past the ring's end writes its row through the ring's
    LAST entry instead of wrapping: the row's own place keeps the row that
    was there a ring ago, and the reading side sees that stale row as the
    newest."""
    import jax.numpy as jnp
    from distributed_training_sandbox_tpu.serving import engine as E
    real = E._paged_attend

    def faulty(q, k, v, *, pages, apos, view=None, **kw):
        if view is not None and q.shape[1] == 1:
            page = kw["pk"].shape[1]
            past = apos // page >= pages.shape[1]
            pages = jnp.where(past, pages[:, -1:], pages)
        return real(q, k, v, pages=pages, apos=apos, view=view, **kw)

    return _patched(E, "_paged_attend", faulty)


def rotary_on_the_full_layer():
    """A decode step rotates q and k in the full-attention layer too: the
    new row's key is cached so, and its query scores so."""
    from distributed_training_sandbox_tpu.models import swa_moe as W
    from distributed_training_sandbox_tpu.serving import engine as E
    tables, qkv, last = E._ragged_rope_tables, W.attention_qkv, []

    def rope_tables(positions, head_dim, theta):
        last[:] = [tables(positions, head_dim, theta)]
        return last[0]

    def attention_qkv(x, layer, *, cfg, rope):
        if rope is None and x.shape[1] == 1:
            rope = last[0]
        return qkv(x, layer, cfg=cfg, rope=rope)

    stack = contextlib.ExitStack()
    stack.enter_context(_patched(E, "_ragged_rope_tables", rope_tables))
    stack.enter_context(_patched(W, "attention_qkv", attention_qkv))
    return stack


def attention_gate_left_out():
    """A decode step does not gate its heads' outputs (``sigmoid(gate)``
    read as 1)."""
    import jax.numpy as jnp
    from distributed_training_sandbox_tpu.models import swa_moe as W
    real = W.attention_output

    def faulty(attn, gate, x, layer, *, cfg):
        if x.shape[1] == 1:
            gate = jnp.full_like(gate, 40.0)
        return real(attn, gate, x, layer, cfg=cfg)

    return _patched(W, "attention_output", faulty)


def _decode_route(route_of):
    """``mla_moe.expert_mlp`` with ``route`` replaced by ``route_of(real
    route)`` in a decode step."""
    from distributed_training_sandbox_tpu.models import mla_moe as M
    real_mlp, real_route = M.expert_mlp, M.route

    def faulty(r2, layer, *, cfg, valid=None):
        if r2.shape[1] != 1:
            return real_mlp(r2, layer, cfg=cfg, valid=valid)
        with _patched(M, "route", route_of(real_route)):
            return real_mlp(r2, layer, cfg=cfg, valid=valid)

    return _patched(M, "expert_mlp", faulty)


def bias_used_as_a_weight():
    """A decode step weighs the chosen experts by ``score + bias``, the
    quantity that chose them, instead of by their scores."""
    import jax
    import jax.numpy as jnp

    def route_of(real):
        def route(r2, w_router, cfg, *, bias):
            _, idx = real(r2, w_router, cfg, bias=bias)
            with jax.default_matmul_precision("highest"):
                s = jax.nn.sigmoid(r2.astype(jnp.float32)
                                   @ w_router.astype(jnp.float32)) + bias
            top = jnp.take_along_axis(s, idx, axis=-1)
            w = cfg.routed_scaling_factor * top \
                / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
            held = cfg.expert_offset + jnp.arange(cfg.held_experts)
            hit = idx[:, :, None] == held[None, None, :]
            return jnp.sum(jnp.where(hit, w[:, :, None], 0.0), axis=1), idx
        return route

    return _decode_route(route_of)


def route_scale_left_out():
    """A decode step's routing weights sum to 1 and not to
    ``routed_scaling_factor`` (the published ``route_scale``)."""
    def route_of(real):
        def route(r2, w_router, cfg, **kw):
            w_held, idx = real(r2, w_router, cfg, **kw)
            return w_held / cfg.routed_scaling_factor, idx
        return route

    return _decode_route(route_of)


#: name -> (the fault, the engine program it changes)
FAULTS = {
    "window_ignored": (window_ignored, "decode"),
    "window_off_by_a_page": (window_off_by_a_page, "decode"),
    "ring_not_wrapped": (ring_not_wrapped, "decode"),
    "rotary_on_the_full_layer": (rotary_on_the_full_layer, "decode"),
    "attention_gate_left_out": (attention_gate_left_out, "decode"),
    "bias_used_as_a_weight": (bias_used_as_a_weight, "decode"),
    "route_scale_left_out": (route_scale_left_out, "decode"),
    "matmuls_in_int8": (matmuls_in_int8, "both"),
}


if __name__ == "__main__":
    # the older file's ``main`` (``benchmarks/run.py`` with a fault of its
    # module's table planted), handed this table
    with _patched(_older, "FAULTS", FAULTS):
        sys.exit(_older.main(sys.argv[1:]))
