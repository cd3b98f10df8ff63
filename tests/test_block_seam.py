"""The seam between ``serving/`` and the block modules under ``models/``:
one declared list of layer kinds a block, and every name the serving loop
(``engine._paged_block_forward``), the pool (``kv_pool``) and the engine
read of a block module.  Import-only: no engine is built, nothing compiles.
"""

import importlib
import json
import math
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from distributed_training_sandbox_tpu.models import transformer as T
from distributed_training_sandbox_tpu.serving import engine as E
from distributed_training_sandbox_tpu.serving import kv_pool

ROOT = Path(__file__).resolve().parents[1]

#: block module -> its benchmark configuration
BLOCKS = {
    "mla_moe": "pangu-ultra-moe-ep32-serve",
    "gdn_hybrid": "olmo-hybrid-7b-l12-serve",
    "gdn_moe": "qwen3-next-80b-ep16-l24-serve",
    "swa_moe": "trinity-large-ep32-l5-serve",
    "ssm_moe": "granite-4.0-h-small-ep4-l10-serve",
    "cca_moe": "zaya1-8b-l16-serve",
    "loop_dense": "ouro-2.6b-serve",
}

KINDS = {"full", "window", "latent", "linear", "conv_full"}

#: what ``serving/`` reads of every block module ...
BLOCK_NAMES = (
    "layer_kinds", "refuse", "embed", "rope_tables", "NOPE_KINDS",
    "mixer_input", "attention_scale", "attention_output", "mlp",
    "final_norm", "COUNTERS", "DEVICE_COUNTERS", "COUNTS_FROM_ZERO")
#: ... and of one that has layers of a kind
NAMES_BY_KIND = {
    "full": ("attention_qkv", "PAGED_ATTENTION_SCOPE"),
    "window": ("attention_qkv", "WINDOW_ATTENTION_SCOPE"),
    "latent": ("latent_qkv", "absorb_queries", "attend_paged",
               "unabsorb_values", "row_width"),
    "linear": ("linear_mixer_output",),
    "conv_full": ("attention_qkv", "PAGED_ATTENTION_SCOPE", "tail_shape"),
}
#: ... and of one whose layers run several passes (``cfg.layer_passes``)
PASS_NAMES = ("pass_end", "exit_choice")
#: what they read of ``cfg.linear_mixer``
MIXER_NAMES = (
    "state_shape", "slot_shape", "tail_shape", "slot_state_bytes",
    "pack_state", "unpack_state", "linear_inputs", "recurrent_step",
    "chunked_scan", "step_kernel", "step_kernel_engages", "COUNTERS")


def _benchmark_config(name: str):
    fields = json.loads(
        (ROOT / "benchmarks" / "configs" / f"{name}.json").read_text()
    )["fields"]
    return T.TransformerConfig(**{
        **fields, "dtype": getattr(jnp, fields.get("dtype", "bfloat16"))})


@pytest.mark.parametrize("block", sorted(BLOCKS))
def test_a_block_declares_its_layer_kinds_and_what_the_loop_reads(block):
    mod = importlib.import_module(
        f"distributed_training_sandbox_tpu.models.{block}")
    cfg = _benchmark_config(BLOCKS[block])
    assert cfg.block_module is mod

    # one entry a WEIGHT layer; the pool counts caches, one a layer a pass
    kinds = mod.layer_kinds(cfg)
    assert isinstance(kinds, tuple) and set(kinds) <= KINDS
    assert len(kinds) == cfg.num_hidden_layers
    passes = cfg.layer_passes
    assert passes == (4 if block == "loop_dense" else 1)
    assert all(hasattr(mod, name) for name in PASS_NAMES) == (passes > 1)
    kinds = kinds * passes
    assert kv_pool.layer_kinds(cfg) == kinds

    wanted = BLOCK_NAMES + tuple(
        name for kind in sorted(set(kinds)) for name in NAMES_BY_KIND[kind])
    assert [name for name in wanted if not hasattr(mod, name)] == []
    assert set(mod.NOPE_KINDS) <= KINDS
    lin = cfg.linear_mixer
    assert (lin is not None) == ("linear" in kinds)
    assert bool(cfg.state_slots) == bool(
        set(kinds) & set(kv_pool.SLOT_KINDS))
    if lin is not None:
        assert [name for name in MIXER_NAMES if not hasattr(lin, name)] == []

    # the counters the loop returns, by what the kinds bring
    device = mod.DEVICE_COUNTERS
    assert E.device_counters(cfg) == device
    assert device == mod.COUNTERS[:len(device)]
    tail = ("state_slot_steps",) * ("linear" in kinds) \
        + ("conv_tail_slot_steps",) * ("conv_full" in kinds) \
        + ("window_rows_read", "full_rows_read") * ("window" in kinds) \
        + ("ut_passes", "exit_step_sum", "early_exit_rows") * (passes > 1)
    assert device[len(device) - len(tail):] == tail

    # the pool is sized from the same list
    n_paged = sum(kind != "linear" for kind in kinds)
    n_linear, n_window = kinds.count("linear"), kinds.count("window")
    n_conv = kinds.count("conv_full")
    assert kv_pool.paged_layers(cfg) == n_paged
    assert kv_pool.slot_state_bytes(cfg) == (
        n_linear * lin.slot_state_bytes(cfg) if n_linear else 0) + (
        n_conv * math.prod(mod.tail_shape(cfg))
        * jnp.dtype(cfg.dtype).itemsize if n_conv else 0)
    bufs = jax.eval_shape(lambda: kv_pool.PagedKVPool(
        cfg, 5, 16, **({"n_slots": 2} if n_linear + n_conv else {}),
        **({"n_pages_window": 3} if n_window else {})).bufs)
    assert len(bufs.k) == n_paged
    assert [a.shape[0] for a in bufs.k] == [
        3 if kind == "window" else 5 for kind in kinds if kind != "linear"]
    assert (bufs.v is None) == ("latent" in kinds)
    assert len(bufs.state or ()) == n_linear
    assert len(bufs.conv or ()) == n_linear + n_conv
    assert all(a.shape[0] == 2
               for a in (bufs.state or ()) + (bufs.conv or ()))


def test_every_layer_of_the_dense_block_is_full():
    cfg = T.TINY_LM
    assert cfg.block_module is None
    assert kv_pool.layer_kinds(cfg) == ("full",) * cfg.num_hidden_layers
    assert kv_pool.paged_layers(cfg) == cfg.num_hidden_layers
    assert E.device_counters(cfg) == ()


def test_serving_asks_a_config_facts_and_never_a_blocks_name():
    """``serving/`` and the program-hash script read ``layer_kinds`` and
    what a block module declares; none tests ``cfg.<block's name>``."""
    by_name = re.compile(
        r"cfg\.(mla_moe|gdn_hybrid|gdn_moe|swa_moe|ssm_moe|cca_moe|"
        r"loop_dense)\b")
    files = sorted((ROOT / "distributed_training_sandbox_tpu"
                    / "serving").glob("*.py"))
    files.append(ROOT / "scripts" / "serving_program_hash.py")
    assert len(files) > 5
    hits = [f"{path.relative_to(ROOT)}:{n}: {line.strip()}"
            for path in files
            for n, line in enumerate(path.read_text().splitlines(), 1)
            if by_name.search(line)]
    assert hits == []
