"""Faults planted in the timed path of the looped dense block, to show that
the comparison which decides ``correct`` separates them from the sound
program: in the rehearsal (``test_bench_loop_dense.py``) and on the chip::

    python3 tests/benchmark/loop_dense_faults.py <fault> --workload \\
        serve-loop-reasoning --seed <n> --seconds 8 --probe '{}'

runs ``benchmarks/run.py`` with the fault in place (``--probe`` prints the
check's distances and no result line; without it, with ``--trace 0``, the
run prints the harness's own result line, ``correct`` false).  Four of the
planted faults touch DECODE steps only (a chunk of more than one row runs
the sound code, so a request's first token is sound and the prefill program
is the sound one); ``one_pass_fewer`` builds both programs, and the pool,
with ``total_ut_steps - 1`` passes; the reference is as it is.  The first
two are what a change that saves memory or time would do and what this
block must not: they change the served tokens.  ``every_product_in_int8``
is the CONTROL the cell's limits are set against: the program recomputed in
the nearest precision below the one the configuration states.
"""

from __future__ import annotations

import contextlib
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from tests.benchmark.cca_moe_faults import _int8  # noqa: E402
from tests.benchmark.gdn_hybrid_faults import _patched  # noqa: E402
from tests.benchmark.gdn_moe_faults import matmuls_in_int8  # noqa: E402


def _LD():
    from distributed_training_sandbox_tpu.models import loop_dense
    return loop_dense


def _in_decode(name, change):
    """The block's ``name`` under ``change(real, *args, **kw)`` where the
    rows are a decode step's (one a slot); a prefill chunk's run the sound
    code."""
    LD = _LD()
    real = getattr(LD, name)

    def faulty(x, *args, **kw):
        if x.shape[1] != 1:
            return real(x, *args, **kw)
        return change(real, x, *args, **kw)

    return _patched(LD, name, faulty)


def kv_shared_across_passes():
    """A decode step's pass ``t`` writes and reads pass ``t - 1``'s keys and
    values (the same rows' K/V of the pass before, so its cache holds what
    the pass before's holds): K/V shared across passes, a cache a layer
    where the model keeps one a (pass, layer).  Pass 0 is sound."""
    made = []

    def change(real, r, layer, *, cfg, rope=None):
        q, k, v, gate = real(r, layer, cfg=cfg, rope=rope)
        # the calls of one forward come in cache order: pass-major
        i, L = len(made) % (cfg.total_ut_steps * cfg.num_hidden_layers), \
            cfg.num_hidden_layers
        if i == 0:
            made.clear()
        made.append((k, v))
        return (q, *made[i - L], gate) if i >= L else (q, k, v, gate)

    return _in_decode("attention_qkv", change)


def final_norm_left_out_between_passes():
    """A decode step's pass ``t + 1`` starts from pass ``t``'s residual
    stream as it is; the gate and the head still read the normed state."""
    def change(real, x, params, t, exits, *, cfg):
        _, exits = real(x, params, t, exits, cfg=cfg)
        return x, exits

    return _in_decode("pass_end", change)


def sublayer_output_norm_left_out():
    """A decode step adds the attention's output to the residual stream
    without its sandwich norm."""
    def change(real, attn, gate, x, layer, *, cfg):
        from distributed_training_sandbox_tpu.models.transformer import _dense
        B, S = attn.shape[:2]
        return x + _dense(cfg)(attn.astype(x.dtype).reshape(B, S, -1),
                               layer["wo"])

    return _in_decode("attention_output", change)


def head_fed_the_first_passes_state():
    """A decode step's head reads ``h_0`` whatever the gates say."""
    def change(real, x, params, t, exits, *, cfg):
        h, (_, *rest) = real(x, params, t, exits, cfg=cfg)
        return h, (h if exits is None else exits[0], *rest)

    return _in_decode("pass_end", change)


def one_pass_fewer():
    """The program built with ``total_ut_steps - 1`` passes (and a pool of
    as many caches fewer): the runner builds the program's config from the
    cell's fields through ``harness.model_config``; the reference reads the
    fields as they are."""
    from benchmarks import harness
    real = harness.model_config
    return _patched(harness, "model_config", lambda fields: real(
        {**fields, "total_ut_steps": int(fields["total_ut_steps"]) - 1}))


@contextlib.contextmanager
def every_product_in_int8():
    """THE CONTROL: the program recomputed in the nearest precision below
    the one the configuration states, over ALL its products and in both
    programs.  Every matrix of a layer is multiplied through ``_dense``:
    ``matmul_precision`` int8 rounds its rows and the weight's columns to
    int8 (``matmuls_in_int8``).  This one also rounds the head's matrix, a
    scale a vocabulary column, and the rows entering it; the embedding's
    rows under the lookup; the exit gate's weight; and a head's query, key
    and value, a scale a head of a row: what attention multiplies, and
    what the pages cache.  What stays in bf16: the probabilities inside the
    attention kernels."""
    from distributed_training_sandbox_tpu.models import transformer as T
    LD = _LD()
    real_qkv, real_embed = LD.attention_qkv, LD.embed
    real_end, real_choice = LD.pass_end, LD.exit_choice
    real_head = T._output_embedding

    def qkv(r, layer, *, cfg, rope=None):
        q, k, v, gate = real_qkv(r, layer, cfg=cfg, rope=rope)
        return _int8(q, -1), _int8(k, -1), _int8(v, -1), gate

    def end(x, params, t, exits, *, cfg):
        gate = params["exit_gate"]
        return real_end(x, {**params, "exit_gate": {
            **gate, "w": _int8(gate["w"], -1)}}, t, exits, cfg=cfg)

    def choice(exits, valid, *, cfg):
        x, counts = real_choice(exits, valid, cfg=cfg)
        return _int8(x, -1), counts

    with contextlib.ExitStack() as stack:
        stack.enter_context(matmuls_in_int8())
        for name, new in (("attention_qkv", qkv), ("pass_end", end),
                          ("exit_choice", choice),
                          ("embed", lambda params, ids, cfg: _int8(
                              real_embed(params, ids, cfg), -1))):
            stack.enter_context(_patched(LD, name, new))
        stack.enter_context(_patched(
            T, "_output_embedding", lambda params, cfg: real_head(
                {**params, "lm_head": _int8(params["lm_head"], -2)}, cfg)))
        yield


#: name -> (the fault, the engine program it changes)
FAULTS = {
    "kv_shared_across_passes": (kv_shared_across_passes, "decode"),
    "final_norm_left_out_between_passes": (
        final_norm_left_out_between_passes, "decode"),
    "sublayer_output_norm_left_out": (sublayer_output_norm_left_out,
                                      "decode"),
    "head_fed_the_first_passes_state": (head_fed_the_first_passes_state,
                                        "decode"),
    "one_pass_fewer": (one_pass_fewer, "both"),
    "every_product_in_int8": (every_product_in_int8, "both"),
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
