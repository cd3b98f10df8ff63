"""Faults planted in the timed path of the gated delta-rule hybrid with
expert layers, to show that the comparison which decides ``correct``
separates them from the sound program: in the rehearsal
(``test_bench_gdn_moe.py``) and on the chip::

    python3 tests/benchmark/gdn_moe_faults.py <fault> --workload \\
        serve-hybrid-moe-longgen --seed <n> --seconds 8 --probe '{}'

runs ``benchmarks/run.py`` with the fault in place (``--probe`` prints the
check's distances and no result line; without it, with ``--trace 0``, the
run prints the harness's own result line, ``correct`` false).  Every
planted fault touches DECODE steps only (a chunk of more than one row runs
the sound code); the reference is as it is.  ``matmuls_in_int8`` is no
planted line but the program as written, computing in the nearest
precision below the one the configuration states, in both programs.
"""

from __future__ import annotations

import contextlib
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from tests.benchmark.gdn_hybrid_faults import _patched, state_in_bf16  # noqa: E402,F401
from tests.benchmark.mla_moe_faults import renormalise_over_held  # noqa: E402,F401


def beta_with_the_factor_2():
    """A decode step takes ``beta = 2 sigmoid(.)``: the older hybrid's
    range, which this block's config does not ask for."""
    from distributed_training_sandbox_tpu.models import gdn_hybrid as G
    real = G.linear_inputs

    def faulty(x, layer, tail, valid, *, cfg):
        q, k, v, g, beta, new_tail = real(x, layer, tail, valid, cfg=cfg)
        if x.shape[1] == 1:
            beta = 2 * beta
        return q, k, v, g, beta, new_tail

    return _patched(G, "linear_inputs", faulty)


def value_heads_on_the_wrong_key_head():
    """A decode step pairs value head ``r`` with key head ``(r + 1) %
    n_k``, not ``r // 2``."""
    import jax.numpy as jnp
    from distributed_training_sandbox_tpu.models import gdn_hybrid as G
    real = G.recurrent_step

    def faulty(q, k, v, g, beta, state):
        n, nk = v.shape[1], q.shape[1]
        wrong = (jnp.arange(n) + 1) % nk
        return real(q[:, wrong], k[:, wrong], v, g, beta, state)

    return _patched(G, "recurrent_step", faulty)


def attention_gate_left_out():
    """A decode step's full-attention layers do not gate their heads'
    outputs (``sigmoid(gate)`` read as 1)."""
    import jax.numpy as jnp
    from distributed_training_sandbox_tpu.models import gdn_moe as N
    real = N.attention_output

    def faulty(attn, gate, x, layer, *, cfg):
        if x.shape[1] == 1:
            gate = jnp.full_like(gate, 40.0)
        return real(attn, gate, x, layer, cfg=cfg)

    return _patched(N, "attention_output", faulty)


def rotary_over_the_whole_head():
    """A decode step rotates all of a head's dims instead of the first
    quarter: the new row's key is cached so, and its query scores so."""
    from distributed_training_sandbox_tpu.models import gdn_moe as N
    tables, qkv = N.rope_tables, N.attention_qkv
    whole = lambda cfg: cfg.resolved_head_dim  # noqa: E731

    def rope_tables(positions, cfg):
        if positions.shape[1] != 1:
            return tables(positions, cfg)
        with _patched(N, "rotary_dim", whole):
            return tables(positions, cfg)

    def attention_qkv(r, layer, *, cfg, rope):
        if r.shape[1] != 1:
            return qkv(r, layer, cfg=cfg, rope=rope)
        with _patched(N, "rotary_dim", whole):
            return qkv(r, layer, cfg=cfg, rope=rope)

    stack = contextlib.ExitStack()
    stack.enter_context(_patched(N, "rope_tables", rope_tables))
    stack.enter_context(_patched(N, "attention_qkv", attention_qkv))
    return stack


def shared_gate_left_out():
    """A decode step adds the shared expert ungated (its ``sigmoid`` read
    as 1)."""
    from distributed_training_sandbox_tpu.models import mla_moe as M
    real = M.expert_mlp

    def faulty(r2, layer, *, cfg, valid=None):
        if r2.shape[1] == 1:
            layer = {k: v for k, v in layer.items() if k != "ws_sigmoid"}
        return real(r2, layer, cfg=cfg, valid=valid)

    return _patched(M, "expert_mlp", faulty)


def matmuls_in_int8():
    """The program built with ``matmul_precision`` int8 (what ``--probe
    '{"config": {"fields": {"matmul_precision": "int8"}}}'`` asks for, but
    through the result line): the runner builds the program's config from
    the cell's fields through ``harness.model_config``; the reference
    reads the fields as they are and computes in float32 whatever they
    say."""
    from benchmarks import harness
    real = harness.model_config
    return _patched(harness, "model_config", lambda fields: real(
        {**fields, "matmul_precision": "int8"}))


#: name -> (the fault, the engine program it changes)
FAULTS = {
    "state_in_bf16": (state_in_bf16, "decode"),
    "beta_with_the_factor_2": (beta_with_the_factor_2, "decode"),
    "value_heads_on_the_wrong_key_head": (value_heads_on_the_wrong_key_head,
                                          "decode"),
    "attention_gate_left_out": (attention_gate_left_out, "decode"),
    "rotary_over_the_whole_head": (rotary_over_the_whole_head, "decode"),
    "renormalise_over_held": (renormalise_over_held, "decode"),
    "shared_gate_left_out": (shared_gate_left_out, "decode"),
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
