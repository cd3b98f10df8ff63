"""Faults planted in the gated delta-rule hybrid's timed path, to show that
the comparison which decides ``correct`` separates them from the sound
program: in the rehearsal (``test_bench_gdn_hybrid.py``) and on the chip::

    python3 tests/benchmark/gdn_hybrid_faults.py <fault> --workload \\
        serve-hybrid-rollout --seed <n> --seconds 8 --probe '{}'

runs ``benchmarks/run.py`` with the fault in place (``--probe`` prints the
check's distances and no result line).  The first two touch DECODE steps
only, the last two PREFILL chunks only; the reference is as it is.  A
fifth, computing in a lower precision, needs no code: ``--probe
'{"config": {"fields": {"matmul_precision": "int8"}}}'``.
"""

from __future__ import annotations

import contextlib
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


@contextlib.contextmanager
def _patched(obj, name, new):
    old = getattr(obj, name)
    setattr(obj, name, new)
    try:
        yield
    finally:
        setattr(obj, name, old)


def state_in_bf16():
    """A decode step keeps the recurrent state in bfloat16: a lower
    precision than the configuration states (float32).  Rounded with
    ``lax.reduce_precision``: on a TPU XLA drops a cast to bfloat16 and
    back (the first chip probe of this fault read the sound program's
    digits)."""
    from jax import lax
    from distributed_training_sandbox_tpu.models import gdn_hybrid as G
    real = G.recurrent_step

    def faulty(q, k, v, g, beta, state):
        o, s = real(q, k, v, g, beta, state)
        return o, lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)

    return _patched(G, "recurrent_step", faulty)


def beta_without_its_factor_2():
    """A decode step takes ``beta = sigmoid(.)``, not ``2 sigmoid(.)``:
    ``linear_allow_neg_eigval`` ignored."""
    from distributed_training_sandbox_tpu.models import gdn_hybrid as G
    real = G.linear_inputs

    def faulty(x, layer, tail, valid, *, cfg):
        q, k, v, g, beta, new_tail = real(x, layer, tail, valid, cfg=cfg)
        if x.shape[1] == 1:
            beta = beta / 2
        return q, k, v, g, beta, new_tail

    return _patched(G, "linear_inputs", faulty)


def conv_tail_not_carried():
    """A prefill chunk's conv starts from zeros instead of the tail the
    chunk before it left: wrong for the first three rows of every chunk of
    a prompt but its first."""
    import jax.numpy as jnp
    from distributed_training_sandbox_tpu.models import gdn_hybrid as G
    real = G.causal_conv

    def faulty(u, tail, conv_w, n_valid):
        if u.shape[1] > 1:
            tail = jnp.zeros_like(tail)
        return real(u, tail, conv_w, n_valid)

    return _patched(G, "causal_conv", faulty)


def padding_rows_update_state():
    """The rows of a prefill chunk past the prompt's end keep their beta
    and alpha, so the padding of a last chunk writes into the state."""
    import jax.numpy as jnp
    from distributed_training_sandbox_tpu.models import gdn_hybrid as G
    real = G.linear_inputs

    def faulty(x, layer, tail, valid, *, cfg):
        out = real(x, layer, tail, valid, cfg=cfg)
        if x.shape[1] == 1:
            return out
        _, _, _, g, beta, _ = real(x, layer, tail, jnp.ones_like(valid),
                                   cfg=cfg)
        return out[:3] + (g, beta, out[5])

    return _patched(G, "linear_inputs", faulty)


#: name -> (the fault, the engine program it changes)
FAULTS = {"state_in_bf16": (state_in_bf16, "decode"),
          "beta_without_its_factor_2": (beta_without_its_factor_2, "decode"),
          "conv_tail_not_carried": (conv_tail_not_carried, "prefill"),
          "padding_rows_update_state": (padding_rows_update_state,
                                        "prefill")}


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
