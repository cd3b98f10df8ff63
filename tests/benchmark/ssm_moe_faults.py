"""Faults planted in the timed path of the Mamba-2 + attention block with
held experts, to show that the comparison which decides ``correct``
separates them from the sound program: in the rehearsal
(``test_bench_ssm_moe.py``) and on the chip::

    python3 tests/benchmark/ssm_moe_faults.py <fault> --workload \\
        serve-ssm-moe-sessions --seed <n> --seconds 8 --probe '{}'

runs ``benchmarks/run.py`` with the fault in place (``--probe`` prints the
check's distances and no result line; without it, with ``--trace 0``, the
run prints the harness's own result line, ``correct`` false).  Every
planted fault but the attention's scale touches DECODE steps only (a chunk
of more than one row runs the sound code); the reference is as it is.
``matmuls_in_int8`` is no planted line but the program as written,
computing in the nearest precision below the one the configuration states,
in both programs.  ``logits_scaling_left_out`` cannot move a greedy token
(the argmax of ``z`` is the argmax of ``z / 16``): it is here to SHOW that
the check by tokens does not see it; ``tests/test_ssm_moe.py`` holds it on
logits.
"""

from __future__ import annotations

import contextlib
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from tests.benchmark.gdn_hybrid_faults import _patched  # noqa: E402
from tests.benchmark.gdn_moe_faults import matmuls_in_int8  # noqa: E402,F401


def _S():
    from distributed_training_sandbox_tpu.models import ssm_moe
    return ssm_moe


def state_in_bf16():
    """A decode step keeps the recurrent state in bfloat16, the dtype of
    the published cache: a lower precision than the configuration states
    (float32).  Rounded with ``lax.reduce_precision``: on a TPU XLA drops a
    cast to bfloat16 and back."""
    from jax import lax
    S = _S()
    real = S.recurrent_step

    def faulty(xd, Bm, Cm, g, skip, state):
        o, s = real(xd, Bm, Cm, g, skip, state)
        return o, lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)

    return _patched(S, "recurrent_step", faulty)


def decay_after_the_update():
    """A decode step computes ``S' = a (S + x (x) B)``: the decay applied
    to the new row too."""
    import jax.numpy as jnp
    S = _S()

    def faulty(xd, Bm, Cm, g, skip, state):
        B, n, hd = xd.shape
        a = jnp.repeat(jnp.exp(g), hd, axis=-1)[:, None]
        s = a * (state + Bm[:, :, None] * xd.reshape(B, 1, n * hd))
        return jnp.sum(Cm[:, :, None] * s, axis=1).reshape(B, n, hd) + skip, s

    return _patched(S, "recurrent_step", faulty)


def _decode_inputs(change):
    """``linear_inputs`` of a decode step (one row) under ``change(layer)
    -> (layer, context)``; a chunk's runs the sound code."""
    S = _S()
    real = S.linear_inputs

    def faulty(r, layer, tail, valid, *, cfg):
        if r.shape[1] != 1:
            return real(r, layer, tail, valid, cfg=cfg)
        layer, ctx = change(layer)
        with ctx:
            return real(r, layer, tail, valid, cfg=cfg)

    return _patched(S, "linear_inputs", faulty)


def dt_without_softplus():
    """A decode step takes ``dt + dt_bias`` as the step size, no
    softplus: negative where the sum is, so a decay above 1."""
    import jax
    return _decode_inputs(lambda layer: (
        layer, _patched(jax.nn, "softplus", lambda x: x)))


def conv_bias_left_out():
    """A decode step's conv adds no bias."""
    import jax.numpy as jnp
    return _decode_inputs(lambda layer: (
        {**layer, "conv_b": jnp.zeros_like(layer["conv_b"])},
        contextlib.nullcontext()))


def dskip_left_out():
    """A decode step's output has no ``Dskip * x`` term."""
    import jax.numpy as jnp
    return _decode_inputs(lambda layer: (
        {**layer, "Dskip": jnp.zeros_like(layer["Dskip"])},
        contextlib.nullcontext()))


def residual_multiplier_left_out():
    """A decode step adds its mixers' and MLPs' outputs unscaled
    (``residual_multiplier`` read as 1)."""
    S = _S()
    real = S.add_scaled

    def faulty(x, y, cfg):
        if x.shape[1] != 1:
            return real(x, y, cfg)
        return (x.astype("float32") + y.astype("float32")).astype(x.dtype)

    return _patched(S, "add_scaled", faulty)


def logits_scaling_left_out():
    """The engine's logits are not divided by ``logits_scaling``.  The
    greedy token cannot change (module docstring)."""
    import dataclasses
    from distributed_training_sandbox_tpu.serving import engine as E
    real = E._all_logits
    return _patched(E, "_all_logits", lambda params, x, cfg: real(
        params, x, dataclasses.replace(cfg, logits_scaling=1.0)))


def attention_scale_one_over_sqrt_hd():
    """The attention layer scores at ``1/sqrt(head_dim)``, what every other
    block's does, not at ``attention_multiplier`` (1/128 at the published
    head of 128, 11.3 x smaller): in BOTH programs, the scale being the
    layer's and not a row's."""
    S = _S()
    return _patched(S, "attention_scale", lambda cfg: None)


def rotary_applied():
    """A decode step rotates its query and its new key (split-half over the
    whole head, theta ``rope_theta``) where the block has no position
    embedding: the new row's key is cached so, and its query scores so."""
    from distributed_training_sandbox_tpu.models import mla_moe as M
    from distributed_training_sandbox_tpu.serving import engine as E
    S = _S()
    tables, qkv = S.rope_tables, S.attention_qkv

    def rope_tables(positions, cfg):
        if positions.shape[1] != 1:
            return tables(positions, cfg)
        return E._ragged_rope_tables(positions, cfg.resolved_head_dim,
                                     cfg.rope_theta)

    def attention_qkv(r, layer, *, cfg, rope=None):
        q, k, v, gate = qkv(r, layer, cfg=cfg)
        if r.shape[1] != 1:
            return q, k, v, gate
        return M._rope(q, *rope), M._rope(k, *rope), v, gate

    stack = contextlib.ExitStack()
    stack.enter_context(_patched(S, "rope_tables", rope_tables))
    stack.enter_context(_patched(S, "attention_qkv", attention_qkv))
    return stack


#: name -> (the fault, the engine program it changes)
FAULTS = {
    "state_in_bf16": (state_in_bf16, "decode"),
    "dt_without_softplus": (dt_without_softplus, "decode"),
    "decay_after_the_update": (decay_after_the_update, "decode"),
    "conv_bias_left_out": (conv_bias_left_out, "decode"),
    "dskip_left_out": (dskip_left_out, "decode"),
    "residual_multiplier_left_out": (residual_multiplier_left_out, "decode"),
    "logits_scaling_left_out": (logits_scaling_left_out, "both"),
    "attention_scale_one_over_sqrt_hd": (attention_scale_one_over_sqrt_hd,
                                         "both"),
    "rotary_applied": (rotary_applied, "decode"),
    "matmuls_in_int8": (matmuls_in_int8, "both"),
}


def main(argv) -> int:
    """``benchmarks/run.py`` with the fault planted.  The run must prepare
    its platform before anything imports JAX, and a fault imports the
    program: so it is planted from inside the run's own
    ``prepare_platform`` call, right after that has done its work."""
    import runpy
    from benchmarks import harness
    name, rest = argv[0], argv[1:]
    real, planted = harness.prepare_platform, contextlib.ExitStack()

    def prepare(chips, rehearse_cpu):
        real(chips, rehearse_cpu)
        planted.enter_context(FAULTS[name][0]())

    sys.argv = [str(ROOT / "benchmarks/run.py"), *rest]
    with planted, _patched(harness, "prepare_platform", prepare):
        try:
            runpy.run_path(sys.argv[0], run_name="__main__")
        except SystemExit as e:
            return int(e.code or 0)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
