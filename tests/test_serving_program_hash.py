"""``scripts/serving_program_hash.py``: a Mosaic kernel's payload embeds
its callers' source lines, and ``canonical`` leaves them out."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "scripts"))

import serving_program_hash as H  # noqa: E402


def _double(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0


def _call(x):
    return pl.pallas_call(
        _double, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x)


def caller_one(x):
    return _call(x) + 1.0


def caller_two(x):
    y = x          # another caller, on other lines
    return _call(y) + 1.0


def _lowered(fn) -> str:
    x = jax.ShapeDtypeStruct((8, 128), jnp.float32)
    def step(x):            # one module name whatever ``fn`` is called
        return fn(x)
    return jax.jit(step).trace(x).lower(lowering_platforms=("tpu",)).as_text()


def test_canonical_text_leaves_the_callers_source_lines_out():
    one, two = _lowered(caller_one), _lowered(caller_two)
    assert "tpu_custom_call" in one
    assert one != two                       # the payloads differ ...
    assert H.canonical(one) == H.canonical(two)         # ... by locations only
    assert H.canonical(one) != one


def test_canonical_text_still_tells_kernels_apart():
    def other(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 3.0

    def caller(x):
        return pl.pallas_call(
            other, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x) + 1.0

    assert H.canonical(_lowered(caller)) != H.canonical(_lowered(caller_one))
