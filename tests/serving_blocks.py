"""The eight blocks the serving engine builds, at a tiny size, float32,
seeded weights: the configurations that the blocks' own test files
(``tests/test_mla_moe.py``, ``tests/test_gdn_hybrid.py``,
``tests/test_gdn_moe.py``, ``tests/test_swa_moe.py``,
``tests/test_ssm_moe.py``, ``tests/test_cca_moe.py``,
``tests/test_loop_dense.py``) and the tests that run over ALL blocks share,
keyed as the benchmark keys its plain float32 references
(``benchmarks/reference/<architecture>.py``)."""

import importlib
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from distributed_training_sandbox_tpu.models import transformer as T  # noqa: E402

_HYBRID = dict(
    vocab_size=256, hidden_size=64, intermediate_size=160,
    num_hidden_layers=4, rms_norm_eps=1e-6, tie_word_embeddings=False,
    nope_interval=0, full_attention_interval=4, linear_key_head_dim=8,
    linear_value_head_dim=16, linear_conv_kernel_dim=4)

#: block kind -> the fields ``TransformerConfig`` gets
FIELDS = {
    "dense_gqa": dict(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        nope_interval=2, rms_norm_eps=1e-6, rope_theta=5e6),
    "mla_moe": dict(
        vocab_size=512, hidden_size=64, intermediate_size=160,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
        rope_theta=10000.0, rms_norm_eps=1e-5, tie_word_embeddings=False,
        nope_interval=0, q_lora_rank=48, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        first_k_dense_replace=1, moe_intermediate_size=24, router_width=16,
        n_routed_experts=4, expert_offset=4, n_shared_experts=1,
        num_experts_per_tok=3, norm_topk_prob=True,
        routed_scaling_factor=2.5, sandwich_norm=True),
    "gdn_hybrid": dict(
        _HYBRID, num_attention_heads=4, num_key_value_heads=4,
        linear_num_key_heads=3, linear_num_value_heads=3,
        linear_allow_neg_eigval=True),
    "gdn_moe": dict(
        _HYBRID, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        rope_theta=1e4, linear_num_key_heads=2, linear_num_value_heads=4,
        num_experts=4, router_width=16, expert_offset=4,
        num_experts_per_tok=3, moe_intermediate_size=32,
        shared_expert_intermediate_size=32, norm_topk_prob=True,
        partial_rotary_factor=0.25),
    "swa_moe": dict(
        vocab_size=256, hidden_size=64, intermediate_size=96,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, rope_theta=1e4, rms_norm_eps=1e-5,
        tie_word_embeddings=False, nope_interval=0, sliding_window=8,
        global_attn_every_n_layers=2, num_dense_layers=1, num_experts=4,
        router_width=16, expert_offset=4, num_experts_per_tok=3,
        moe_intermediate_size=32, num_shared_experts=1, norm_topk_prob=True,
        routed_scaling_factor=2.448, sandwich_norm=True, mup_enabled=True),
    "ssm_moe": dict(
        vocab_size=256, hidden_size=64, intermediate_size=32,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        rms_norm_eps=1e-5, tie_word_embeddings=True, nope_interval=0,
        layer_types=("mamba", "mamba", "attention", "mamba"),
        mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16, mamba_d_conv=4,
        num_local_experts=4, router_width=12, expert_offset=4,
        num_experts_per_tok=3, shared_intermediate_size=48,
        embedding_multiplier=12.0, residual_multiplier=0.22,
        attention_multiplier=0.03125, logits_scaling=4.0),
    "cca_moe": dict(
        vocab_size=256, hidden_size=64, intermediate_size=None,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, rope_theta=5e6, rms_norm_eps=1e-5,
        tie_word_embeddings=True, nope_interval=0, cca_time0=2, cca_time1=2,
        partial_rotary_factor=0.5, router_hidden_size=32, num_experts=4,
        router_width=8, expert_offset=4, num_experts_per_tok=1,
        moe_intermediate_size=32),
    # the exit threshold below the published 1.0, so that rows leave at
    # different passes and every device counter moves
    "loop_dense": dict(
        vocab_size=512, hidden_size=64, intermediate_size=160,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
        head_dim=16, rope_theta=1e6, rms_norm_eps=1e-6,
        tie_word_embeddings=False, nope_interval=0, total_ut_steps=3,
        early_exit_threshold=0.5),
}
BLOCKS = tuple(FIELDS)


def make(block: str, seed: int = 0, scale: float = 3.0):
    """``(fields, cfg, params)`` of ``block``, weights scaled as the
    benchmark scales them (greedy tokens then sit far from a tie)."""
    fields = FIELDS[block]
    cfg = T.TransformerConfig(**fields, dtype=jnp.float32, remat=False)
    params = jax.tree.map(lambda x: (x * scale).astype(x.dtype),
                          T.init_params(jax.random.key(seed), cfg))
    return fields, cfg, params


def reference_tokens(block: str, fields, params, prompt, tokens) -> list:
    """What the plain float32 reference decodes greedily after ``prompt``,
    as far as ``tokens`` goes: its argmax at every position of ``prompt +
    tokens``, so equal to ``tokens`` exactly when they are its own greedy
    continuation (by induction over the positions)."""
    ref = importlib.import_module(f"benchmarks.reference.{block}")
    seq = np.concatenate([prompt, np.asarray(tokens[:-1], np.int32)])
    pos = len(prompt) - 1 + np.arange(len(tokens))
    z = ref.logits_at(params, jnp.asarray(seq, jnp.int32), jnp.asarray(pos),
                      fields, block=len(seq))
    return [int(t) for t in np.asarray(jnp.argmax(z, axis=-1))]


def serve_logits(params, cfg, prompt, n_new, *, kernel=False, chunk=16,
                 slots=3, slot=1, bufs=None, page=8, seq=64):
    """Chunked prefill and then decode of ONE request of a block module
    through the engine's own cores (``engine._paged_forward``), tapped for
    logits: ``(logits (n_new, V), the pool's buffers afterwards, the
    device-side counters summed over the decode steps, the request's page
    row)``.  ``bufs``: the pool to start from (default: a zeroed one)."""
    from distributed_training_sandbox_tpu.serving import engine as E
    from distributed_training_sandbox_tpu.serving.kv_pool import PagedKVPool
    P = seq // page
    pool = PagedKVPool(cfg, slots * P + 1, page,
                       **({"n_slots": slots} if cfg.state_slots else {}))
    pages = np.zeros((slots, P), np.int32)
    pages[slot] = pool.allocator.alloc(P)
    bufs = pool.bufs if bufs is None else bufs

    @jax.jit
    def prefill(bufs, ids, pos, plen):
        apos = pos + jnp.arange(chunk, dtype=jnp.int32)[None, :]
        x, bufs, _ = E._paged_forward(
            params, ids, cfg, bufs, jnp.asarray(pages[slot:slot + 1]), apos,
            apos < plen, paged_kernel=kernel, slot=jnp.int32(slot))
        return E._all_logits(params, x, cfg), bufs

    @jax.jit
    def decode(bufs, toks, lengths, active):
        x, bufs, counts = E._paged_forward(
            params, toks[:, None], cfg, bufs, jnp.asarray(pages),
            lengths[:, None], active[:, None], paged_kernel=kernel)
        return E._last_logits(params, x, cfg), bufs, counts

    n = len(prompt)
    for pos in range(0, n, chunk):
        ids = np.zeros((1, chunk), np.int32)
        part = prompt[pos:pos + chunk]
        ids[0, :len(part)] = part
        z, bufs = prefill(bufs, jnp.asarray(ids), jnp.int32(pos),
                          jnp.int32(n))
    out = [z[0, (n - 1) % chunk]]
    active = np.zeros(slots, bool)
    active[slot] = True
    counted = np.zeros(len(E.device_counters(cfg)), np.int64)
    for i in range(n_new - 1):
        toks = np.full(slots, 7, np.int32)      # inactive slots: any token
        toks[slot] = int(jnp.argmax(out[-1]))
        lengths = np.zeros(slots, np.int32)
        lengths[slot] = n + i
        z, bufs, counts = decode(bufs, jnp.asarray(toks),
                                 jnp.asarray(lengths), jnp.asarray(active))
        out.append(z[slot])
        counted += np.asarray(counts)
    return jnp.stack(out), bufs, counted, pages[slot]
