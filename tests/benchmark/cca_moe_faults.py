"""Faults planted in the timed path of the block of compressed convolutional
attention over a top-1 expert layer, to show that the comparison which
decides ``correct`` separates them from the sound program: in the rehearsal
(``test_bench_cca_moe.py``) and on the chip::

    python3 tests/benchmark/cca_moe_faults.py <fault> --workload \\
        serve-cca-moe-longgen --seed <n> --seconds 8 --probe '{}'

runs ``benchmarks/run.py`` with the fault in place (``--probe`` prints the
check's distances and no result line; without it, with ``--trace 0``, the
run prints the harness's own result line, ``correct`` false).  Three of the
planted faults touch DECODE steps only (a chunk of more than one row runs
the sound code); ``tail_zeroed_at_a_chunk_boundary`` is a prefill chunk's,
whose decode steps then continue from what it left; the reference is as it
is.  ``matmuls_in_int8`` is no planted line but the program as written,
computing its two ``_dense`` products a layer in the nearest precision below
the one the configuration states, in both programs;
``every_product_in_int8`` is the CONTROL the cell's limits are set against:
that precision over every product of both programs.
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


def _C():
    from distributed_training_sandbox_tpu.models import cca_moe
    return cca_moe


def _qkv(change, decode: bool):
    """``attention_qkv`` of a decode step (one row), or of a prefill chunk
    (more), under ``change(layer, tail) -> (layer, tail, post)``, ``post``
    mapping the five results; the other program's runs the sound code."""
    C = _C()
    real = C.attention_qkv

    def faulty(r, layer, *, cfg, rope, tail, valid):
        if (r.shape[1] == 1) != decode:
            return real(r, layer, cfg=cfg, rope=rope, tail=tail, valid=valid)
        layer, tail, post = change(layer, tail, cfg)
        return post(real(r, layer, cfg=cfg, rope=rope, tail=tail,
                         valid=valid))

    return _patched(C, "attention_qkv", faulty)


def shifted_half_from_the_current_token():
    """A decode step's second KV head holds ``r_t wv2``, the token's own,
    where it is the PREVIOUS token's: the value shift left out (the tail
    still carries the right half on)."""
    import jax.numpy as jnp

    def change(layer, tail, cfg):
        C, hd = _C().latent_channels(cfg), cfg.resolved_head_dim

        def post(out):
            q, k, v, gate, new_tail = out
            # the row's own ``r_t wv2`` is what it leaves in the tail
            own = new_tail[:, None, 2 * C:2 * C + hd].astype(v.dtype)
            return q, k, jnp.stack([v[:, :, 0], own], axis=2), gate, new_tail

        return layer, tail, post

    return _qkv(change, decode=True)


def tail_zeroed_at_a_chunk_boundary():
    """Every prefill chunk starts from a tail of zeros, not only a
    request's first: the convolutions and the value shift of a later
    chunk's first rows see no row before them."""
    import jax.numpy as jnp
    return _qkv(lambda layer, tail, cfg: (layer, jnp.zeros_like(tail),
                                          lambda out: out), decode=False)


def temperature_left_out():
    """A decode step's keys are cached without their temperature
    (``k_temp`` read as 1)."""
    import jax.numpy as jnp
    return _qkv(lambda layer, tail, cfg: (
        {**layer, "k_temp": jnp.ones_like(layer["k_temp"])}, tail,
        lambda out: out), decode=True)


def top1_weight_renormalised():
    """A decode step weighs its chosen expert by 1: the top-1 score
    renormalised over the chosen, as every other block's routing is."""
    from distributed_training_sandbox_tpu.models import mla_moe as M
    C = _C()
    real_mlp = M.expert_mlp

    def faulty(r2, layer, *, cfg, valid=None):
        if r2.shape[1] != 1:
            return real_mlp(r2, layer, cfg=cfg, valid=valid)
        with _patched(C, "NORM_TOPK_PROB", True):
            return real_mlp(r2, layer, cfg=cfg, valid=valid)

    return _patched(M, "expert_mlp", faulty)


def _int8(a, axis):
    """``a`` rounded to 127 steps either side of zero of the largest
    magnitude along ``axis`` (the product's contraction), and back: what an
    int8 operand with one float scale a channel (a row) holds."""
    import jax.numpy as jnp
    f = a.astype(jnp.float32)
    step = jnp.maximum(jnp.max(jnp.abs(f), axis=axis, keepdims=True),
                       1e-30) / 127.0
    return (jnp.clip(jnp.round(f / step), -127, 127) * step).astype(a.dtype)


def _int8_weights(tree: dict) -> dict:
    """Every matrix of a layer (or of the top of the tree) as int8 holds
    it, a scale an output channel: the contraction is a matrix's
    second-to-last axis, the grouped convolution's its two taps and ``hd``
    inputs together, the tied embedding's a row's ``H`` (the head's
    contraction; the lookup reads the same rows).  Vectors (norms, biases,
    temperatures) are as they were."""
    out = dict(tree)
    for name, w in tree.items():
        if name == "embed":
            out[name] = _int8(w, -1)
        elif name == "conv1_w":
            G, _, hd, _ = w.shape
            out[name] = _int8(w.reshape(G, 2 * hd, hd), -2).reshape(w.shape)
        elif getattr(w, "ndim", 0) >= 2:
            out[name] = _int8(w, -2)
    return out


@contextlib.contextmanager
def every_product_in_int8():
    """THE CONTROL: the program recomputed in the nearest precision below
    the one the configuration states, over ALL its products and in both
    programs.  ``matmuls_in_int8`` reaches the two ``_dense`` products a
    layer, 2.5% of a layer's weights; this one also rounds every matrix to
    int8 where it is used (the projections, both convolutions, the
    router's four, the 16 experts' three, the tied embedding under the
    lookup and under the head), the rows entering the expert layer and the
    head, a scale a row, and a head's query, key and value, a scale a head
    of a row: what attention multiplies, and what the pages cache.  What
    stays in bf16: the activation inside the grouped expert kernel between
    its two products, and the probabilities inside the attention
    kernels."""
    from distributed_training_sandbox_tpu.models import mla_moe as M
    from distributed_training_sandbox_tpu.models import transformer as T
    C = _C()

    def on_layer(name, post=lambda out: out):
        real = getattr(C, name)

        def lowered(*args, **kw):
            args = tuple(_int8_weights(a) if isinstance(a, dict) and "w_qkv"
                         in a else a for a in args)
            return post(real(*args, **kw))

        return _patched(C, name, lowered)

    def qkv_in_int8(out):
        q, k, v, gate, new_tail = out
        return _int8(q, -1), _int8(k, -1), _int8(v, -1), gate, new_tail

    real_embed, real_norm = C.embed, C.final_norm
    real_head, real_experts = T._output_embedding, M.expert_mlp
    with contextlib.ExitStack() as stack:
        stack.enter_context(matmuls_in_int8())
        stack.enter_context(on_layer("attention_qkv", qkv_in_int8))
        for name in ("attention_output", "mlp"):
            stack.enter_context(on_layer(name))
        stack.enter_context(_patched(
            C, "embed", lambda params, ids, cfg: _int8(
                real_embed(params, ids, cfg), -1)))
        stack.enter_context(_patched(
            C, "final_norm", lambda x, params, cfg: _int8(
                real_norm(x, params, cfg), -1)))
        stack.enter_context(_patched(
            T, "_output_embedding", lambda params, cfg: real_head(
                _int8_weights(params), cfg)))
        stack.enter_context(_patched(
            M, "expert_mlp", lambda r2, layer, **kw: real_experts(
                _int8(r2, -1), layer, **kw)))
        yield


#: name -> (the fault, the engine program it changes)
FAULTS = {
    "shifted_half_from_the_current_token": (
        shifted_half_from_the_current_token, "decode"),
    "tail_zeroed_at_a_chunk_boundary": (tail_zeroed_at_a_chunk_boundary,
                                        "prefill"),
    "temperature_left_out": (temperature_left_out, "decode"),
    "top1_weight_renormalised": (top1_weight_renormalised, "decode"),
    "matmuls_in_int8": (matmuls_in_int8, "both"),
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
