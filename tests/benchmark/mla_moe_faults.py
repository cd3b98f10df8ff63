"""Faults planted in the latent block's timed path, to show that the
comparison which decides ``correct`` separates them from the sound program:
in the rehearsal (``test_bench_mla_moe.py``) and on the chip::

    python3 tests/benchmark/mla_moe_faults.py <fault> --workload \\
        serve-mla-moe-longgen --seed <n> --seconds 8 --probe '{}'

runs ``benchmarks/run.py`` with the fault in place (``--probe`` prints the
check's distances and no result line).  Each fault touches DECODE steps
only: prefill and the reference are as they are.  A fourth, computing in a
lower precision, needs no code: ``--probe '{"config": {"fields":
{"matmul_precision": "int8"}}}'``.
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


def skip_shared_expert():
    """A decode step leaves the shared expert out of an expert layer."""
    from distributed_training_sandbox_tpu.models import mla_moe as M
    from distributed_training_sandbox_tpu.models import transformer as T
    real = M.expert_mlp

    def faulty(r2, layer, *, cfg, valid=None):
        m, counts = real(r2, layer, cfg=cfg, valid=valid)
        if r2.shape[1] == 1:
            m = m - M._swiglu(r2, layer["ws_gate"], layer["ws_up"],
                              layer["ws_down"], T._dense(cfg))
        return m, counts

    return _patched(M, "expert_mlp", faulty)


def renormalise_over_held():
    """A decode step normalises the routing weights over the chosen experts
    that are HELD here instead of over all the chosen."""
    import jax.numpy as jnp
    from distributed_training_sandbox_tpu.models import mla_moe as M
    real_mlp, real_route = M.expert_mlp, M.route

    def route(r2, w_router, cfg):
        w_held, idx = real_route(r2, w_router, cfg)
        tot = jnp.sum(w_held, axis=-1, keepdims=True)
        return cfg.routed_scaling_factor * w_held / (tot + 1e-20), idx

    def faulty(r2, layer, *, cfg, valid=None):
        if r2.shape[1] != 1:
            return real_mlp(r2, layer, cfg=cfg, valid=valid)
        with _patched(M, "route", route):
            return real_mlp(r2, layer, cfg=cfg, valid=valid)

    return _patched(M, "expert_mlp", faulty)


def scores_without_the_rope_columns():
    """The decode kernel scores against a row's first ``rank`` columns
    (512 of 576): the shared rotary key never reaches a score."""
    from distributed_training_sandbox_tpu.ops import paged_attention as PA
    real = PA.paged_latent_attention_decode

    def faulty(qa, pool, pages, lengths, *, rank, **kw):
        return real(qa.at[..., rank:].set(0), pool, pages, lengths,
                    rank=rank, **kw)

    return _patched(PA, "paged_latent_attention_decode", faulty)


FAULTS = {"skip_shared_expert": skip_shared_expert,
          "renormalise_over_held": renormalise_over_held,
          "scores_without_the_rope_columns": scores_without_the_rope_columns}


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
        planted.enter_context(FAULTS[name]())

    sys.argv = [str(ROOT / "benchmarks/run.py"), *rest]
    with planted, _patched(harness, "prepare_platform", prepare):
        try:
            runpy.run_path(sys.argv[0], run_name="__main__")
        except SystemExit as e:
            return int(e.code or 0)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
