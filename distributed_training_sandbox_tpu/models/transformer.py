"""Decoder-only transformer LM, the "real model" of the framework.

Twin of the reference's FSDP/fp8 model path, which instantiates
SmolLM3-3B-class HF causal LMs from config with random init, bf16,
``use_cache=False`` (reference ``fsdp/train_fsdp.py:61-64``,
``fp8/fp8_benchmark.py:34-44``).  Here the model is a pure-functional JAX
pytree so every parallelism strategy (DDP / ZeRO / FSDP / PP / quantized)
can manipulate params directly:

  * SmolLM3-class architecture: RMSNorm, rotary attention with a NoPE
    interval (every 4th layer skips RoPE), grouped-query attention, gated
    SwiGLU MLP, tied embeddings.
  * **Scanned layers**: per-layer params are stacked on a leading axis and
    the forward runs ``lax.scan`` over them — one compiled layer body
    regardless of depth (compile time and HLO size stay O(1) in layers, and
    FSDP-style per-layer gathers become one collective inside the scan body).
  * ``jax.checkpoint`` around the scan body = the reference's
    activation-memory story (README.md:26-33): only per-layer boundaries
    are live across the backward.
  * Attention impl selectable: "xla" (einsum + causal mask — runs anywhere,
    XLA fuses on TPU) or "flash" (fused Pallas TPU kernel, the MXU/HBM-
    friendly path for seq 8192).

Shapes use (batch, seq, hidden) with weights stored (in, out) so the hot
matmuls are plain ``x @ w`` on the MXU.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field, replace
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.collectives import axis_size
from ..utils.profiling import scope


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 128_256
    hidden_size: int = 2048
    intermediate_size: int = 11_008
    num_hidden_layers: int = 36
    num_attention_heads: int = 16
    num_key_value_heads: int = 4
    head_dim: int | None = None
    rope_theta: float = 5_000_000.0
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = True
    # Every nope_interval-th layer (0-indexed: layers where (i+1) % interval
    # == 0) skips RoPE — SmolLM3's NoPE scheme.  0 disables (RoPE everywhere).
    nope_interval: int = 4
    dtype: Any = jnp.bfloat16
    remat: bool = True
    # "full" recomputes the whole layer in backward, except that under
    # attention_impl="flash" the splash kernel's own residuals stay
    # resident under EVERY policy (its output, S·nq·hd in the model dtype,
    # and its log-sum-exp, S·nq fp32, per layer: FLASH_RESIDUALS), so the
    # recomputation re-runs norm, QKV and RoPE but not the O(S²) kernel;
    # "save_attn" also keeps each layer's tagged attention output
    # (+S·nq·hd per layer, the tensor after the kernel's transposes);
    # "save_dots" keeps every
    # matmul output resident — the backward recomputes only cheap
    # elementwise ops, trading ~all of remat's extra forward FLOPs for
    # O(layers · S · (heads+ffn)) activation memory.  The
    # rematerialisation trade the reference's reshard_after_forward
    # comments gesture at (fsdp/train_fsdp.py:84-88), applied to FLOPs
    # instead of gathers.
    # "save_dots_q8" is save_dots with int8-QUANTIZED saved activations
    # (ops/quant.quantized_residual): every projection output makes an
    # int8 round-trip whose quantized pair is what remat keeps — half
    # save_dots' activation bytes, same recompute savings, at the cost
    # of per-row int8 noise in the forward (the attack on the r3
    # save_dots×int8 OOM wall).
    remat_policy: str = "full"
    # "full" | "save_attn" | "save_dots" | "save_dots_q8"
    # Host offload of the policy-saved activations (memory planner,
    # --offload opt_act): the named saved tensors ride
    # ``save_and_offload_only_these_names`` to pinned host memory instead
    # of staying resident in HBM — only meaningful for the *named*-save
    # policies (save_attn / save_dots_q8).  Backends without a
    # pinned_host space (CPU sim) silently keep the plain save policy
    # (``memory_plan.offload.supports_host_offload``).
    offload_activations: bool = False
    # "ring" = exact causal attention over a sequence-sharded mesh axis
    # (``sp_axis``) — context parallelism for sequences past one chip's
    # HBM; only valid inside shard_map (see parallel/sequence.py).
    attention_impl: str = "xla"  # "xla" | "flash" | "ring"
    sp_axis: str | None = None  # mesh axis the sequence is sharded on
    # Ring q-chunk: bound each fold's fp32 score buffer to
    # (B, n, ring_block_q, S_local); 0 = unchunked.  Must divide S_local
    # (S_local/2 for the zigzag layout).
    ring_block_q: int = 0
    # Ring KV layout: "contiguous" (rank-order chunks) or "zigzag"
    # (balanced stripes — ~half the ring's score FLOPs; batches must be
    # fed through parallel.sequence.zigzag_shuffle).
    ring_layout: str = "contiguous"
    # Cross-entropy logits budget: None materializes full (B, S, vocab)
    # fp32 logits (the reference's documented ~4 GB spikes,
    # README.md:28-33); an int streams the tokens through the head in row
    # blocks over the whole vocabulary, a block's fp32 logits capped at
    # B·S·chunk·4 bytes (streamed_softmax_xent).
    loss_vocab_chunk: int | None = None
    # Projection-matmul precision: "bf16", or int8 with dynamic absmax
    # scaling (forward quantized, backward bf16) — the reference's fp8
    # benchmark knob (fp8_benchmark.py:47) with v5e's native low-precision
    # format.  "int8_pallas" routes through the hand-tiled Pallas kernel.
    # The fp8 tier is the recipe-faithful Float8Linear twin (e4m3 fwd /
    # e5m2 bwd per-tensor scales, ops/quant.fp8_dense): "fp8" (dynamic
    # scaling), "fp8_delayed" (amax-history delayed scaling, depth
    # ``fp8_amax_history_len``), "fp8_pallas" (Pallas forward kernel).
    # "bf16" | "int8" | "int8_pallas" | "int8_bwd" | "int8_pallas_bwd"
    #        | "fp8" | "fp8_delayed" | "fp8_pallas"
    matmul_precision: str = "bf16"
    # Delayed-scaling amax history depth for "fp8_delayed" (torchao's
    # ``delayed`` recipe rolls this many step amaxes; ignored by the
    # dynamic fp8 variants).
    fp8_amax_history_len: int = 16
    gated_mlp: bool = True  # duck-types as FlopsConfig for utils.flops
    # Mixture-of-experts MLP (parallel/expert.py): 0 = dense.  With
    # n_experts > 0 every layer's MLP becomes a top-1 switch-MoE of
    # ``n_experts`` experts with ``moe_ffn`` (default intermediate_size)
    # hidden width; ``ep_axis`` shards experts across that mesh axis
    # (None = all experts local).  The Switch load-balance aux loss is
    # summed over layers and added to lm_loss with ``moe_aux_weight``.
    n_experts: int = 0
    moe_ffn: int | None = None
    moe_capacity_factor: float = 2.0
    moe_aux_weight: float = 0.01
    # "grouped" (GShard-style per-group one-hot matmuls — fastest on TPU,
    # no gather/scatter) | "sort" (global-capacity sort dispatch) |
    # "einsum" (whole-chunk one-hot oracle == grouped with one group).
    moe_dispatch: str = "grouped"
    moe_group_size: int = 128  # tokens per dispatch group ("grouped" only)
    # experts per token: 1 = Switch, 2+ = GShard top-k (normalized gates,
    # active FLOPs ×k; requires the grouped dispatch).
    moe_top_k: int = 1
    # Router-health knobs (ST-MoE): z-loss weight on mean
    # logsumexp(router logits)² — keeps logits small so the balance aux
    # keeps gradient signal; and a router LR multiplier (<1 slows the
    # router relative to the experts, the standard fix when the router
    # collapses faster than experts can differentiate).
    moe_router_z_weight: float = 0.0
    moe_router_lr_mult: float = 1.0
    ep_axis: str | None = None
    # The latent-attention + held-experts block (``models/mla_moe.py``;
    # names as in the published configs of that family).
    # ``kv_lora_rank`` > 0 selects it: multi-head latent attention
    # (queries through a ``q_lora_rank`` bottleneck, keys and values
    # through one ``kv_lora_rank`` latent plus one shared rotary key of
    # ``qk_rope_head_dim``), sandwich norms, ``first_k_dense_replace``
    # leading SwiGLU layers of ``intermediate_size`` and then expert
    # layers: a router of ``router_width`` sigmoid scores (the experts of
    # the WHOLE layer), ``num_experts_per_tok`` chosen, of which this
    # program HOLDS ``n_routed_experts``, ids ``expert_offset`` onwards
    # (one rank's share under expert parallelism), each of
    # ``moe_intermediate_size``, plus ``n_shared_experts`` applied to
    # every token.  Serving only (``serving/engine.py``) and the
    # cache-less ``forward``; the training factories refuse it.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    first_k_dense_replace: int = 0
    moe_intermediate_size: int = 0
    router_width: int = 0
    n_routed_experts: int = 0
    expert_offset: int = 0
    n_shared_experts: int = 0
    num_experts_per_tok: int = 0
    norm_topk_prob: bool = False
    routed_scaling_factor: float = 1.0
    sandwich_norm: bool = False
    # The gated delta-rule hybrid block (``models/gdn_hybrid.py``; the
    # ``linear_*`` names as in the published configs of that family).
    # ``linear_key_head_dim`` > 0 selects it: layer i (0-based) is a
    # full-attention layer where (i + 1) % ``full_attention_interval`` == 0
    # (the published ``layer_types`` as a period) and a linear-attention
    # layer otherwise, whose per-request state is one float32
    # (heads, key dim, value dim) matrix and a conv tail, whatever the
    # request's length.  Serving only and the cache-less ``forward``.
    full_attention_interval: int = 0
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 0
    linear_allow_neg_eigval: bool = False
    # The gated delta-rule hybrid with an expert layer under every mixer
    # (``models/gdn_moe.py``; names as in the published configs of that
    # family).  ``num_experts`` > 0 beside ``linear_key_head_dim`` > 0
    # selects it: ``num_experts`` routed experts are HELD here (ids
    # ``expert_offset`` onwards of the router's ``router_width``, each of
    # ``moe_intermediate_size``, ``num_experts_per_tok`` chosen a token)
    # beside one sigmoid-gated shared expert of
    # ``shared_expert_intermediate_size``; the full-attention layers gate
    # their heads' outputs and rotate the first ``partial_rotary_factor``
    # of each head's dims.  Serving only and the cache-less ``forward``.
    num_experts: int = 0
    shared_expert_intermediate_size: int = 0
    partial_rotary_factor: float = 1.0
    # Sliding-window and full GQA attention mixed in one stack, output
    # gated, over held and shared experts (``models/swa_moe.py``; names as
    # in the published configs of that family).  ``sliding_window`` > 0
    # selects it: layer i (0-based) attends its whole context where
    # (i + 1) % ``global_attn_every_n_layers`` == 0 and its last
    # ``sliding_window`` positions otherwise (rotary embedding on those
    # layers alone); the first ``num_dense_layers`` layers have a SwiGLU of
    # ``intermediate_size``, the rest a sigmoid router of ``router_width``
    # with a selection bias, ``num_experts`` routed experts HELD here (ids
    # ``expert_offset`` onwards) and ``num_shared_experts`` shared ones, all
    # of ``moe_intermediate_size``; ``mup_enabled`` scales the embedding by
    # sqrt(hidden).  Serving only and the cache-less ``forward``.
    sliding_window: int = 0
    global_attn_every_n_layers: int = 0
    num_dense_layers: int = 0
    num_shared_experts: int = 0
    mup_enabled: bool = False
    # Mamba-2 state-space layers beside GQA attention layers with no
    # position embedding, over held and shared experts
    # (``models/ssm_moe.py``; names as in the published configs of that
    # family).  ``mamba_d_state`` > 0 selects it: layer i is an attention
    # layer where ``layer_types[i] == "attention"`` and a Mamba-2 layer
    # otherwise (the list may be the published one, longer than
    # ``num_hidden_layers``: its first entries are run;
    # ``mamba_n_heads`` heads of ``mamba_d_head`` = ``mamba_expand``
    # x hidden, one B/C group of ``mamba_d_state``, a conv of
    # ``mamba_d_conv`` with bias, the chunked form at blocks of
    # ``mamba_chunk_size``), whose per-request state is one float32 (heads,
    # head dim, state dim) tensor and a conv tail.  Every layer's MLP is a
    # softmax router of ``router_width`` with ``num_local_experts`` routed
    # experts of ``intermediate_size`` HELD here (ids ``expert_offset``
    # onwards) and a shared expert of ``shared_intermediate_size``.  The
    # family's four multipliers: the embedding times
    # ``embedding_multiplier``, every mixer's and MLP's output times
    # ``residual_multiplier``, attention scores times
    # ``attention_multiplier`` (0: 1/sqrt(head_dim)), logits divided by
    # ``logits_scaling``.  Serving only and the cache-less ``forward``.
    layer_types: tuple = ()
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_d_state: int = 0
    mamba_d_conv: int = 0
    mamba_n_groups: int = 1
    mamba_expand: int = 2
    mamba_chunk_size: int = 256
    num_local_experts: int = 0
    shared_intermediate_size: int = 0
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float = 0.0
    logits_scaling: float = 1.0
    # Compressed convolutional attention over a top-1 expert layer with an
    # MLP router and no shared expert (``models/cca_moe.py``; names as in
    # the published configs of that family).  ``cca_time0`` > 0 selects it:
    # queries and keys are latents that two causal convolutions mix over
    # the sequence (a depthwise one of kernel ``cca_time0``, then one
    # grouped by head of kernel ``cca_time1``), half of a token's values
    # are the previous token's, the first ``partial_rotary_factor`` of a
    # head's dims rotate; every layer's MLP is a router MLP of
    # ``router_hidden_size`` over ``router_width`` experts, of which
    # ``num_experts`` of ``moe_intermediate_size`` are HELD here (ids
    # ``expert_offset`` onwards) and ONE is chosen a token, weighed by its
    # softmax probability as it is (``norm_topk_prob`` False).  A request
    # holds K/V pages AND a conv tail a slot in every layer.  The family
    # has no dense MLP: ``intermediate_size`` is None.  Serving only and
    # the cache-less ``forward``.
    cca_time0: int = 0
    cca_time1: int = 0
    router_hidden_size: int = 0
    # A dense stack whose layers run several times with the same weights
    # (``models/loop_dense.py``; names as in the published configs of that
    # family).  ``total_ut_steps`` > 0 selects it: every token runs the
    # ``num_hidden_layers`` weight layers that many times, a pass after a
    # pass, each pass with K/V of its own in every layer
    # (:attr:`layer_passes`); sandwich norms, the final norm and an exit
    # gate at the end of every pass, and the head reads the state of the
    # first pass whose cumulated exit probability reaches
    # ``early_exit_threshold`` (1.0: the last pass's).  Untied head.
    # Serving only and the cache-less ``forward``.
    total_ut_steps: int = 0
    early_exit_threshold: float = 1.0

    def __post_init__(self):
        # a configuration file brings a list; the config must hash
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if self.block_module is not None:
            self.block_module.check_config(self)
        # Covers every construction path incl. dataclasses.replace: a
        # sequence-sharded config with a local-chunk attention impl would
        # silently never attend across chunk boundaries.
        if self.sp_axis is not None and self.attention_impl != "ring":
            raise ValueError(
                f"sp_axis={self.sp_axis!r} (sequence sharded) requires "
                f"attention_impl='ring', got {self.attention_impl!r} "
                f"(parallel.sequence.sp_config sets both)")
        if self.attention_impl == "ring" and self.sp_axis is None:
            raise ValueError(
                "attention_impl='ring' needs sp_axis set to the mesh axis "
                "the sequence is sharded on, and must run inside shard_map "
                "(see parallel.sequence.sp_config)")
        if self.ring_layout not in ("contiguous", "zigzag"):
            raise ValueError(f"unknown ring_layout {self.ring_layout!r}")
        if self.moe_top_k > 1 and self.moe_dispatch != "grouped":
            raise ValueError(
                f"moe_top_k={self.moe_top_k} requires moe_dispatch="
                f"'grouped' (got {self.moe_dispatch!r})")
        if self.moe_router_z_weight and not self.moe_aux_weight:
            raise ValueError(
                "moe_router_z_weight rides the aux-loss channel scaled "
                "by moe_aux_weight — set moe_aux_weight > 0 too")
        if self.offload_activations and (
                not self.remat
                or self.remat_policy not in ("save_attn", "save_dots_q8")):
            raise ValueError(
                "offload_activations redirects NAMED saved tensors to "
                "host memory — it needs remat=True and remat_policy in "
                "('save_attn', 'save_dots_q8'); "
                f"got remat={self.remat}, "
                f"remat_policy={self.remat_policy!r}")

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.hidden_size // self.num_attention_heads

    @property
    def mla_moe(self) -> bool:
        """The latent-attention + held-experts block (``models/mla_moe.py``)
        instead of the dense GQA one."""
        return self.kv_lora_rank > 0

    @property
    def gdn_hybrid(self) -> bool:
        """A block of gated delta-rule layers and full-attention layers,
        whose requests hold state slots beside pages: the one of
        ``models/gdn_hybrid.py`` or, with :attr:`gdn_moe`, of
        ``models/gdn_moe.py``."""
        return self.linear_key_head_dim > 0

    @property
    def gdn_moe(self) -> bool:
        """The gated delta-rule hybrid with an expert layer under every
        mixer (``models/gdn_moe.py``)."""
        return self.gdn_hybrid and self.num_experts > 0

    @property
    def swa_moe(self) -> bool:
        """Sliding-window and full attention layers over held and shared
        experts (``models/swa_moe.py``), whose window layers cache their
        rows in a page class of their own."""
        return self.sliding_window > 0

    @property
    def ssm_moe(self) -> bool:
        """Mamba-2 state-space layers beside attention layers, over held
        and shared experts (``models/ssm_moe.py``), whose requests hold
        state slots beside pages as the gated delta-rule hybrids' do."""
        return self.mamba_d_state > 0

    @property
    def cca_moe(self) -> bool:
        """Compressed convolutional attention over a top-1 expert layer
        (``models/cca_moe.py``), whose every layer's requests hold a conv
        tail in a slot beside their K/V pages."""
        return self.cca_time0 > 0

    @property
    def loop_dense(self) -> bool:
        """A dense stack whose layers run ``total_ut_steps`` times with the
        same weights (``models/loop_dense.py``), each pass with K/V pages
        of its own."""
        return self.total_ut_steps > 0

    @property
    def layer_passes(self) -> int:
        """How many times a token runs the weight layers, each pass with
        caches of its own: a layer holds this many caches a request
        (``serving/kv_pool.py``).  1 for every block but the looped one."""
        return self.total_ut_steps or 1

    @property
    def linear_mixer(self):
        """The module that holds the linear mixer of a block whose
        requests keep STATE SLOTS beside pages (what a linear mixer
        brings: ``serving/engine._paged_block_forward``'s docstring; the
        state's and the tail's shapes, the step and the scan); None for a
        block whose every layer is paged."""
        if self.gdn_hybrid:
            from . import gdn_hybrid
            return gdn_hybrid
        if self.ssm_moe:
            from . import ssm_moe
            return ssm_moe
        return None

    @property
    def state_slots(self) -> bool:
        """Whether a request of this block holds a slot beside its pages:
        a linear mixer's state and conv tail (:attr:`linear_mixer`), or a
        conv tail alone under paged attention (:attr:`cca_moe`)."""
        return self.gdn_hybrid or self.ssm_moe or self.cca_moe

    @property
    def held_experts(self) -> int:
        """Routed experts of an expert layer that this program holds, under
        whichever name the block's published config counts them."""
        return self.n_routed_experts or self.num_experts \
            or self.num_local_experts

    @property
    def block_module(self):
        """The module that holds a block other than the dense GQA one
        (``check_config``, ``init_params``, ``param_count``,
        ``hidden_states``, ``refuse``, and for the serving loop
        ``layer_kinds`` and what ``serving/engine._paged_block_forward``'s
        docstring lists); None for the dense block."""
        if self.mla_moe:
            from . import mla_moe
            return mla_moe
        if self.gdn_moe:
            from . import gdn_moe
            return gdn_moe
        if self.gdn_hybrid:
            from . import gdn_hybrid
            return gdn_hybrid
        if self.swa_moe:
            from . import swa_moe
            return swa_moe
        if self.ssm_moe:
            from . import ssm_moe
            return ssm_moe
        if self.cca_moe:
            from . import cca_moe
            return cca_moe
        if self.loop_dense:
            from . import loop_dense
            return loop_dense
        return None

    def param_count(self) -> int:
        if self.block_module is not None:
            return self.block_module.param_count(self)
        h, hd = self.hidden_size, self.resolved_head_dim
        attn = h * hd * (self.num_attention_heads * 2
                         + self.num_key_value_heads * 2)
        if self.n_experts:
            F = self.moe_ffn or self.intermediate_size
            mlp = self.n_experts * 3 * h * F + h * self.n_experts
        else:
            mlp = 3 * h * self.intermediate_size
        norms = 2 * h
        per_layer = attn + mlp + norms
        embed = self.vocab_size * h
        head = 0 if self.tie_word_embeddings else embed
        return self.num_hidden_layers * per_layer + embed + head + h


# SmolLM3-3B-class config (~3.1 B params), the reference's FSDP benchmark
# model (fsdp/train_fsdp.py:61-64).
SMOLLM3_3B = TransformerConfig()

# Single-chip flagship: the 3B architecture (same hidden/heads/vocab/MLP
# geometry, so per-layer compute is identical) truncated to 8 layers to fit
# one 16 GB v5e with AdamW state; fused attention + streamed loss head.
SMOLLM3_3B_L8 = TransformerConfig(
    num_hidden_layers=8, attention_impl="flash", loss_vocab_chunk=16_032)

# Switch-MoE flagship: the 3B-L8 geometry with its MLP split into 8
# experts of ffn 2752 (dense MLP FLOPs 4-ways active) — the MoE A/B's
# configuration as a named constant.
SMOLLM3_3B_L8_MOE = TransformerConfig(
    num_hidden_layers=8, attention_impl="flash", loss_vocab_chunk=16_032,
    n_experts=8, moe_ffn=2752, moe_dispatch="grouped")

# Qwen3-4B-class geometry — the reference fp8 benchmark's default model
# family (``fp8/modal_app.py:40``: Qwen/Qwen3-4B): hidden 2560, 9728
# FFN, 32/8 GQA heads at head_dim 128, 151936 vocab, rope 1M.  Geometry
# class only (random init like every config here); Qwen3's QK-norm is
# not modeled — the benchmark-relevant shapes are.
QWEN3_4B = TransformerConfig(
    vocab_size=151_936, hidden_size=2560, intermediate_size=9728,
    num_hidden_layers=36, num_attention_heads=32, num_key_value_heads=8,
    head_dim=128, rope_theta=1_000_000.0, nope_interval=0)

# One-chip flagship sibling (same per-layer geometry, 6 layers — the
# L8 trick applied to the 4B family).
QWEN3_4B_L6 = TransformerConfig(
    vocab_size=151_936, hidden_size=2560, intermediate_size=9728,
    num_hidden_layers=6, num_attention_heads=32, num_key_value_heads=8,
    head_dim=128, rope_theta=1_000_000.0, nope_interval=0,
    attention_impl="flash", loss_vocab_chunk=15_194)

# Llama-3.2-1B / Llama-3.1-8B geometry classes — the remaining fp8
# benchmark target families (``fp8/fp8_benchmark.py:34-37``).  The 1B
# trains WHOLE on one 16 GB v5e (1.24 B params); the 8B is the
# multi-chip configuration (FSDP/TP it over a mesh).
LLAMA32_1B = TransformerConfig(
    vocab_size=128_256, hidden_size=2048, intermediate_size=8192,
    num_hidden_layers=16, num_attention_heads=32, num_key_value_heads=8,
    head_dim=64, rope_theta=500_000.0, nope_interval=0,
    attention_impl="flash", loss_vocab_chunk=16_032)
LLAMA31_8B = TransformerConfig(
    vocab_size=128_256, hidden_size=4096, intermediate_size=14_336,
    num_hidden_layers=32, num_attention_heads=32, num_key_value_heads=8,
    head_dim=128, rope_theta=500_000.0, nope_interval=0,
    tie_word_embeddings=False)

# Smaller siblings for 1-chip benches and CI (same shape family).
SMOLLM3_350M = TransformerConfig(
    vocab_size=49_152, hidden_size=960, intermediate_size=2560,
    num_hidden_layers=32, num_attention_heads=15, num_key_value_heads=5,
    head_dim=64)
TINY_LM = TransformerConfig(
    vocab_size=512, hidden_size=64, intermediate_size=160,
    num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
    rope_theta=10_000.0, dtype=jnp.float32, remat=False)

# Real-text fixture geometry (~70 M params): vocab matches the committed
# corpus tokenizer (data/corpus/tokenizer.json, vocab 8192) so the
# offline real-text path trains it directly — the substrate for the MoE
# quality A/B and the corpus flagship runs (reference trains on real
# TinyStories text, fsdp/utils.py:29-91).
CORPUS_LM = TransformerConfig(
    vocab_size=8192, hidden_size=768, intermediate_size=2048,
    num_hidden_layers=8, num_attention_heads=12, num_key_value_heads=4,
    head_dim=64, rope_theta=10_000.0, nope_interval=0,
    attention_impl="flash")

# 350M-class real-text flagship: the SmolLM3-350M geometry at the corpus
# tokenizer's vocab (49k→8k trims the embedding; ~270 M params remain) —
# the substrate for the ≥500-step real-text flagship run.
CORPUS_350M = TransformerConfig(
    vocab_size=8192, hidden_size=960, intermediate_size=2560,
    num_hidden_layers=32, num_attention_heads=15, num_key_value_heads=5,
    head_dim=64, nope_interval=0, attention_impl="flash")
# 8-layer sibling: depth experiments (4-stage / interleaved pipelines
# need more layers than TINY_LM's 4).
TINY_LM_L8 = replace(TINY_LM, num_hidden_layers=8)


def require_dense_block(cfg, what: str) -> None:
    """Every path that is written for the dense GQA block alone (the
    training factories of ``parallel/``, the one-shot decoder of
    ``models/generate.py``) names itself here and refuses the latent
    block and the gated delta-rule hybrid, rather than run them as dense
    math or fail on a missing key."""
    block = getattr(cfg, "block_module", None)
    if block is not None:
        block.refuse(cfg, what)


# ------------------------------------------------------------------- init

def init_params(key: jax.Array, cfg: TransformerConfig) -> dict:
    """Random init from config — the reference never loads checkpoints
    (``fsdp/train_fsdp.py:61-64``), so neither does the default path here.
    Truncated-normal 0.02 (HF default), out-projections scaled by
    1/sqrt(2·layers) for depth-stable residuals."""
    if cfg.block_module is not None:
        return cfg.block_module.init_params(key, cfg)
    h = cfg.hidden_size
    hd = cfg.resolved_head_dim
    nq, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    L = cfg.num_hidden_layers
    keys = iter(jax.random.split(key, 16))

    def tn(k, shape, std=0.02):
        return (std * jax.random.truncated_normal(k, -2, 2, shape,
                                                  jnp.float32)
                ).astype(cfg.dtype)

    out_std = 0.02 / math.sqrt(2 * L)
    params = {
        "embed": tn(next(keys), (cfg.vocab_size, h)),
        "layers": {
            "ln1": jnp.ones((L, h), cfg.dtype),
            "wq": tn(next(keys), (L, h, nq * hd)),
            "wk": tn(next(keys), (L, h, nkv * hd)),
            "wv": tn(next(keys), (L, h, nkv * hd)),
            "wo": tn(next(keys), (L, nq * hd, h), out_std),
            "ln2": jnp.ones((L, h), cfg.dtype),
        },
        "final_norm": jnp.ones((h,), cfg.dtype),
    }
    if cfg.n_experts:
        E, F = cfg.n_experts, cfg.moe_ffn or cfg.intermediate_size
        params["layers"].update(
            w_router=tn(next(keys), (L, h, E)),
            w_gate=tn(next(keys), (L, E, h, F)),
            w_up=tn(next(keys), (L, E, h, F)),
            w_down=tn(next(keys), (L, E, F, h), out_std))
    else:
        params["layers"].update(
            w_gate=tn(next(keys), (L, h, cfg.intermediate_size)),
            w_up=tn(next(keys), (L, h, cfg.intermediate_size)),
            w_down=tn(next(keys), (L, cfg.intermediate_size, h),
                      out_std))
    if not cfg.tie_word_embeddings:
        params["lm_head"] = tn(next(keys), (h, cfg.vocab_size))
    return params


# ---------------------------------------------------------------- building blocks

def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * w.astype(jnp.float32)).astype(dt)


def _rope_tables(seq_len: int, head_dim: int, theta: float, offset=0,
                 positions=None):
    """``offset`` (may be traced) shifts positions — under sequence
    parallelism each device's chunk starts at rank · S_local.
    ``positions`` overrides with an explicit (seq_len,) global-position
    array (zigzag layout: the chunk is two non-adjacent stripes)."""
    inv_freq = 1.0 / theta ** (jnp.arange(0, head_dim, 2,
                                          dtype=jnp.float32) / head_dim)
    if positions is None:
        positions = offset + jnp.arange(seq_len, dtype=jnp.float32)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, S, n_heads, head_dim); split-half rotation (HF convention)."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    x1, x2 = jnp.split(x, 2, axis=-1)
    c = cos[None, :, None, :]
    s = sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(dt)


def _attention_xla(q, k, v, scale: float) -> jax.Array:
    """Plain causal attention: (B, S, n, hd) → (B, S, n, hd).  Scores in
    fp32 (the numerically load-bearing part); XLA fuses mask+softmax."""
    B, S, nq, hd = q.shape
    nkv = k.shape[2]
    if nq != nkv:  # GQA: repeat kv heads
        rep = nq // nkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bqnh,bknh->bnqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    mask = jnp.tril(jnp.ones((S, S), jnp.bool_))
    scores = jnp.where(mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bnqk,bknh->bqnh", probs, v)


# Checkpoint name the splash kernel tags its forward residuals with (its
# output and its log-sum-exp); resolve_remat_policy keeps it under every
# policy, so a rematerialised layer's backward finds them and the
# kernel's forward runs once a step.
FLASH_RESIDUALS = "splash_residuals"


def flash_backward_blocks(S: int) -> tuple[int, int, int]:
    """``(block_q_dkv, block_kv_dkv, block_kv_dkv_compute)`` of the splash
    kernel's fused backward at a window of ``S`` positions.  That kernel
    writes one dq partial a KV block, ``(S / block_kv_dkv, n_q, S, hd)``
    in q's dtype, so the KV block follows the window: the smallest
    lane-aligned divisor of ``S`` that is at least an eighth of it (eight
    partials or fewer), between 1024 and 4096.  A larger block divides
    the partials' bytes and multiplies the masked work on the diagonal
    (~``block_kv_dkv / S`` of the kernel's); past 4096 rows at head_dim
    128 the kernel's K, V and float32 dk / dv blocks no longer fit a
    v5e's scoped VMEM, and nor does a q block over 1024 beside a 512-row
    compute block.  Swept on the v5e at 2k, 8k and 32k, the kernel alone
    and the step (PERF.md section 6, PR 48)."""
    lanes = S // 128
    least = min(S, 4096, max(1024, S / 8))
    bkv = next(128 * d for d in range(1, lanes + 1)
               if lanes % d == 0 and 128 * d >= least)
    # the compute block must itself be a multiple of 128 that divides bkv
    bkv_c = next(c for c in (512, 384, 256, 128) if bkv % c == 0)
    return min(1024, S), bkv, bkv_c


def _attention_flash(q, k, v, scale: float) -> jax.Array:
    """Fused Pallas TPU attention (splash kernel): never materializes the
    S×S score matrix in HBM, handles GQA natively (no kv repeat), causal
    blocks skipped above the diagonal.  Block sizes 512/1024 measured ~2×
    over the kernel defaults at seq 8192 on v5e.  The backward is ONE
    kernel (the library's fused form) that forms dS once and takes dk, dv
    and a dq partial a KV block from it; the partials are summed outside
    it (``jnp.sum``, which accumulates bfloat16 in float32).  The
    seq-8192 path."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)
    B, S, nq, hd = q.shape
    if S % 128:
        # no quiet hand-over to the einsum path: a run configured for the
        # kernel must not measure something else
        raise ValueError(
            f"attention_impl='flash' needs a sequence length that is a "
            f"multiple of 128 (splash blocks are lane-aligned), got {S}; "
            f"pad the sequence or use attention_impl='xla'")
    bq, bkv = min(512, S), min(1024, S)
    # block_kv_compute must itself be a multiple of 128
    bkv_c = bkv // 2 if bkv % 256 == 0 else bkv
    bq_dkv, bkv_dkv, bkv_dkv_c = flash_backward_blocks(S)
    mask = sm.MultiHeadMask([sm.CausalMask((S, S)) for _ in range(nq)])
    kernel = sk.make_splash_mha_single_device(
        mask=mask,
        block_sizes=sk.BlockSizes(
            block_q=bq, block_kv=bkv, block_kv_compute=bkv_c,
            block_q_dkv=bq_dkv, block_kv_dkv=bkv_dkv,
            block_kv_dkv_compute=bkv_dkv_c,
            use_fused_bwd_kernel=True),
        residual_checkpoint_name=FLASH_RESIDUALS)

    def one(q1, k1, v1):  # (S, n, hd) -> kernel layout (n, S, hd)
        out = kernel(q1.swapaxes(0, 1) * scale, k1.swapaxes(0, 1),
                     v1.swapaxes(0, 1))
        return out.swapaxes(0, 1)

    return jax.vmap(one)(q, k, v)


def _dense(cfg: TransformerConfig):
    """The projection matmul at the configured precision.  Precisions:
    bf16; int8 (XLA fwd); int8_pallas (fused quantize-matmul kernel fwd);
    *_bwd variants additionally run both backward matmuls at int8; the
    fp8 family (fp8 / fp8_delayed / fp8_pallas) runs the Float8Linear
    e4m3-forward/e5m2-backward recipe end to end.

    Under ``remat_policy="save_dots_q8"`` (and only with remat ON —
    without ``jax.checkpoint`` nothing is saved, so the round-trip
    would be pure noise+cost) every output makes the int8 save
    round-trip (``quant.quantized_residual``) so the remat policy keeps
    the int8 pair instead of the bf16 tensor.

    A weight arriving as :class:`ops.collectives.RingShard` (the
    ``overlap="ring_fused"`` FSDP layer hook leaves projection weights
    sharded along their contraction dim) routes through the decomposed
    collective matmul — ``all_gather_matmul``, or its Pallas tile-kernel
    twin when the shard is marked ``impl="pallas"``
    (``overlap="ring_fused_pallas"``) — gather hops interleaved with
    the chunk matmuls instead of a monolithic gather-then-dot."""
    from ..ops import collectives as C
    from ..ops.quant import quantized_residual, resolve_quantized_dense
    base = resolve_quantized_dense(
        cfg.matmul_precision, fp8_history_len=cfg.fp8_amax_history_len)

    def dispatch(a, w):
        if isinstance(w, C.RingShard):
            if w.impl == "pallas":
                return C.all_gather_matmul_pallas(a, w.shard, w.axis_name)
            return C.all_gather_matmul(a, w.shard, w.axis_name)
        return base(a, w)

    if cfg.remat and cfg.remat_policy == "save_dots_q8":
        return lambda a, w: quantized_residual(dispatch(a, w))
    return dispatch


def _qkv_proj(r, layer, *, cfg: TransformerConfig, cos, sin, use_rope,
              tp: int = 1):
    """Normed residual → RoPE'd (q, k, v) — the projection math shared
    by the training layer and the KV-cache decode layer
    (``models/generate.py``), so the two paths cannot drift."""
    B, S, _ = r.shape
    hd = cfg.resolved_head_dim
    nq = cfg.num_attention_heads // tp
    nkv = cfg.num_key_value_heads // tp
    dense = _dense(cfg)
    q = dense(r, layer["wq"]).reshape(B, S, nq, hd)
    k = dense(r, layer["wk"]).reshape(B, S, nkv, hd)
    v = dense(r, layer["wv"]).reshape(B, S, nkv, hd)
    q = jnp.where(use_rope, apply_rope(q, cos, sin), q)
    k = jnp.where(use_rope, apply_rope(k, cos, sin), k)
    return q, k, v


def _mlp_block(r, layer, *, cfg: TransformerConfig):
    """Post-attention MLP (dense SwiGLU or top-k MoE) on the normed
    residual — shared by training and decode.  Returns (mlp, aux)."""
    aux = jnp.zeros((), jnp.float32)
    if cfg.n_experts:
        from ..parallel.expert import moe_mlp
        mlp, aux = moe_mlp(r, layer["w_router"], layer["w_gate"],
                           layer["w_up"], layer["w_down"],
                           axis=cfg.ep_axis,
                           capacity_factor=cfg.moe_capacity_factor,
                           dispatch=cfg.moe_dispatch,
                           group_size=cfg.moe_group_size,
                           top_k=cfg.moe_top_k,
                           matmul_precision=cfg.matmul_precision,
                           router_z_ratio=(cfg.moe_router_z_weight
                                           / cfg.moe_aux_weight
                                           if cfg.moe_router_z_weight
                                           else 0.0))
    else:
        dense = _dense(cfg)
        mlp = dense(jax.nn.silu(dense(r, layer["w_gate"]))
                    * dense(r, layer["w_up"]), layer["w_down"])
    return mlp, aux


def _layer_body(x, layer, *, cfg: TransformerConfig, cos, sin, use_rope,
                tp_axis: str | None = None, tp_overlap: str = "none"):
    """One decoder layer.  ``layer`` holds this layer's (unstacked) params;
    ``use_rope`` is a traced bool scalar (NoPE schedule).

    ``tp_axis``: Megatron tensor parallelism (parallel/tensor.py) — the
    layer weights are LOCAL shards (wq/wk/wv/w_gate/w_up column-sharded,
    wo/w_down row-sharded over that mesh axis) and the two row-parallel
    outputs are psum'd back into the residual stream.

    ``tp_overlap="ring"`` decomposes those two psums into
    psum_scatter + ring all-gather (``ops.collectives.
    decomposed_all_reduce`` over the hidden dim) — bitwise-identical
    values/grads, but the rejoin exposes tp-1 schedulable hops instead
    of one monolithic all-reduce."""
    B, S, h = x.shape
    hd = cfg.resolved_head_dim
    tp = axis_size(tp_axis) if tp_axis else 1
    nq = cfg.num_attention_heads // tp
    dense = _dense(cfg)

    with scope("attn_qkv"):
        r = rms_norm(x, layer["ln1"], cfg.rms_norm_eps)
        q, k, v = _qkv_proj(r, layer, cfg=cfg, cos=cos, sin=sin,
                            use_rope=use_rope, tp=tp)
    scale = 1.0 / math.sqrt(hd)
    with scope("attn_core"):
        if cfg.attention_impl == "flash":
            attn = _attention_flash(q, k, v, scale).astype(x.dtype)
        elif cfg.attention_impl == "ring":  # sp_axis validated in __post_init__
            from ..ops.ring_attention import ring_attention
            attn = ring_attention(q, k, v, cfg.sp_axis, scale=scale,
                                  block_q=cfg.ring_block_q or None,
                                  layout=cfg.ring_layout)
        else:
            attn = _attention_xla(q, k, v, scale).astype(x.dtype)
    from jax.ad_checkpoint import checkpoint_name
    attn = checkpoint_name(attn, "attn_out")
    with scope("attn_out"):
        attn_out = dense(attn.reshape(B, S, nq * hd), layer["wo"])
    if tp_axis:  # Megatron f/g: rejoin the row-parallel partial sums
        from ..ops import collectives as C
        if tp_overlap == "ring":
            _rejoin = lambda v: C.decomposed_all_reduce(v, tp_axis,
                                                        axis=-1)
        elif tp_overlap == "q8":
            # EQuARX two-shot: partial sums ship as int8 codes + scales
            # (~4x fewer bus bytes than the f32 psum), dequant-sum after
            # the wire; backward stays a full-precision psum.
            from ..ops.quant import quantized_all_reduce
            _rejoin = lambda v: quantized_all_reduce(v, tp_axis)
        else:
            _rejoin = lambda v: C.all_reduce(v, tp_axis)
        with scope("tp_attn_psum"):
            attn_out = _rejoin(attn_out)
    x = x + attn_out

    if tp_axis and cfg.n_experts and cfg.ep_axis:
        raise ValueError("shard experts over ep OR split them over "
                         "tp, not both (ep_axis and tp_axis set)")
    # Under TP each rank holds every expert's F/tp slice (tp_specs):
    # routing/dispatch are replicated across the tp group (tokens and
    # router are), the per-expert matmuls produce partial sums, and one
    # psum after combine rejoins them — the Megatron row/column pairing
    # applied inside each expert (dense MLP: the classic pairing).
    with scope("mlp"):
        r = rms_norm(x, layer["ln2"], cfg.rms_norm_eps)
        mlp, aux = _mlp_block(r, layer, cfg=cfg)
    if tp_axis:
        with scope("tp_moe_psum" if cfg.n_experts else "tp_mlp_psum"):
            mlp = _rejoin(mlp)
    return x + mlp, aux


def resolve_remat_policy(cfg: TransformerConfig):
    """cfg.remat_policy name → jax.checkpoint policy (one mapping for
    every scaffold that remats the layer scan — hidden_states and
    parallel/pipeline's stage bodies).

    Under ``attention_impl="flash"`` every policy also keeps the splash
    kernel's forward residuals (``FLASH_RESIDUALS``): re-running the
    kernel costs O(S²), what it saves is O(S) and no larger than the
    boundary activation the scan keeps anyway.

    With ``cfg.offload_activations`` (and a backend that has a
    pinned_host space) the named-save policies become
    ``save_and_offload_only_these_names``: the same tensors survive the
    backward, but parked in host DRAM instead of HBM — the
    remat-activation leg of the memory planner's host offload (the
    kernel's residuals stay on the device)."""
    policies = jax.checkpoint_policies
    kept = [FLASH_RESIDUALS] if cfg.attention_impl == "flash" else []
    if cfg.offload_activations:
        from ..memory_plan.offload import (
            OFFLOADABLE_REMAT_NAMES, supports_host_offload)
        names = OFFLOADABLE_REMAT_NAMES[cfg.remat_policy]
        if supports_host_offload():
            return policies.save_and_offload_only_these_names(
                names_which_can_be_saved=kept,
                names_which_can_be_offloaded=list(names),
                offload_src="device", offload_dst="pinned_host")
        # CPU sim: no host space distinct from device — keep the plain
        # save policy (bitwise-identical math, zero transfers declared)
    if cfg.remat_policy == "save_dots":
        dots = policies.dots_with_no_batch_dims_saveable
        return policies.save_from_both_policies(
            dots, policies.save_only_these_names(*kept)) if kept else dots
    # the tensors a policy saves by name; save_dots_q8's are the int8
    # pairs _dense's round-trip tagged
    names = {"save_attn": ["attn_out"], "save_dots_q8": ["dot_q8"],
             "full": []}[cfg.remat_policy] + kept
    return policies.save_only_these_names(*names) if names else None


def _rope_flags(cfg: TransformerConfig) -> jax.Array:
    """Per-layer use-RoPE flags: SmolLM3 drops RoPE on every
    ``nope_interval``-th layer."""
    idx = jnp.arange(cfg.num_hidden_layers)
    if cfg.nope_interval:
        return (idx + 1) % cfg.nope_interval != 0
    return jnp.ones_like(idx, dtype=jnp.bool_)


# ---------------------------------------------------------------- forward

def forward(params: dict, input_ids: jax.Array, cfg: TransformerConfig,
            *, layer_hook=None, layer_body=None) -> jax.Array:
    """``input_ids`` (B, S) int32 → logits (B, S, vocab) in cfg.dtype.

    ``layer_hook(layer_params) -> layer_params`` runs inside the scan body
    *before* the layer computes — the seam where ZeRO-3/FSDP materialize
    full params from shards (the JAX twin of the reference's module
    forward-pre hooks, ``zero/zero3.py:56-77``).  Because the scan body is
    rematerialized, the hook (and its all_gather) re-runs in the backward
    pass, reproducing the backward pre-hook re-gather.

    ``layer_body`` replaces the decoder-layer computation itself (same
    signature as ``_layer_body``) — the seam where tensor parallelism
    substitutes its Megatron-sharded layer (``parallel/tensor.py``) while
    reusing this scaffold (RoPE tables, NoPE flags, remat, scan, loss).
    """
    x = hidden_states(params, input_ids, cfg, layer_hook=layer_hook,
                      layer_body=layer_body)
    with scope("loss_head"):
        logits = x @ _output_embedding(params, cfg).T
        if cfg.logits_scaling != 1.0:
            logits = (logits.astype(jnp.float32)
                      / cfg.logits_scaling).astype(logits.dtype)
        return logits


def hidden_states(params: dict, input_ids: jax.Array,
                  cfg: TransformerConfig, *, layer_hook=None,
                  layer_body=None, return_aux: bool = False):
    """Trunk only: (B, S) ids → final-norm hidden states (B, S, H).
    ``return_aux=True`` additionally returns the per-layer auxiliary
    losses summed (the MoE load-balance term; 0 for dense layers)."""
    block = cfg.block_module
    if block is not None:
        if layer_hook is not None or layer_body is not None:
            block.refuse(cfg, "a layer hook or a substituted layer body "
                         "(FSDP, ZeRO-3, tensor parallelism)")
        x = block.hidden_states(params, input_ids, cfg)
        return (x, jnp.zeros((), jnp.float32)) if return_aux else x
    B, S = input_ids.shape
    apply_layer = layer_body or _layer_body
    with scope("embed"):
        x = params["embed"].astype(cfg.dtype)[input_ids]
        # Under sequence parallelism S is the LOCAL chunk; RoPE positions
        # and the causal structure use this rank's GLOBAL positions — an
        # offset for contiguous chunks, the stripe-pair position map for
        # zigzag.
        if cfg.sp_axis and cfg.ring_layout == "zigzag":
            from ..ops.ring_attention import zigzag_positions
            cos, sin = _rope_tables(
                S, cfg.resolved_head_dim, cfg.rope_theta,
                positions=zigzag_positions(cfg.sp_axis, S))
        else:
            offset = lax.axis_index(cfg.sp_axis) * S if cfg.sp_axis else 0
            cos, sin = _rope_tables(S, cfg.resolved_head_dim,
                                    cfg.rope_theta, offset)
        flags = _rope_flags(cfg)

    def body(carry, scanned):
        layer, use_rope = scanned
        if layer_hook is not None:
            layer = layer_hook(layer)
        x, aux = apply_layer(carry, layer, cfg=cfg, cos=cos, sin=sin,
                             use_rope=use_rope)
        return x, aux

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False,
                              policy=resolve_remat_policy(cfg))
    x, aux = lax.scan(body, x, (params["layers"], flags))
    with scope("loss_head"):
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    return (x, jnp.sum(aux)) if return_aux else x


def _output_embedding(params: dict, cfg: TransformerConfig) -> jax.Array:
    """Unembedding as (vocab, H) rows (tied: the input embedding itself)."""
    w = params.get("lm_head")
    if w is None:
        return params["embed"].astype(cfg.dtype)
    return w.astype(cfg.dtype).T


def _loss_row_block(tokens: int, vocab: int, chunk: int) -> int:
    """Rows of one block of the streamed head.  ``chunk`` is the head's
    float32 logits budget, ``tokens * chunk * 4`` bytes, spent on whole
    vocabulary rows; a block of 128 rows or more is whole MXU tiles, and
    the blocks are evened out so the last one's padding stays small."""
    rows = min(tokens, max(1, tokens * chunk // vocab))
    tile = 128 if rows >= 128 else 1
    rows -= rows % tile
    even = -(-tokens // -(-tokens // rows))
    return -(-even // tile) * tile


def _xent_row_blocks(x, w_vocab, labels, tokens: int, with_grad: bool):
    """One sweep over row blocks of ``x`` (n, R, H) against the whole
    ``w_vocab`` (V, H); ``labels`` (n, R), negative on padding rows.  Gives
    the mean over ``tokens`` of the negative log-likelihood and,
    ``with_grad``, its gradients ``dx`` (n, R, H) and ``dW`` (V, H) made
    from the same logits: three products with the vocabulary a block, and
    no block's logits outlive it.  ``dW`` is summed over the blocks in
    float32 and left so, for the caller to round once."""
    def block(carry, scanned):
        x_blk, lab = scanned
        live = lab >= 0
        logits = jnp.einsum("rh,vh->rv", x_blk, w_vocab,
                            preferred_element_type=jnp.float32)
        m = jnp.max(logits, axis=-1)
        lse = m + jnp.log(jnp.sum(jnp.exp(logits - m[:, None]), axis=-1))
        gold = jnp.take_along_axis(logits, jnp.maximum(lab, 0)[:, None],
                                   axis=-1)[:, 0]
        nll = jnp.sum(jnp.where(live, lse - gold, 0.0)) / tokens
        if not with_grad:
            return carry + nll, None
        loss, dw = carry
        # softmax / tokens as ONE exp of the shifted logits, 0 on padding
        # rows: both products below recompute d from the logits as they
        # read them, and a division there cost 22 ms a step on a v5e
        shift = jnp.where(live, lse + math.log(tokens), jnp.inf)
        hot = lab[:, None] == jnp.arange(logits.shape[-1])
        d = jnp.exp(logits - shift[:, None]) - jnp.where(hot, 1 / tokens, 0.0)
        d = d.astype(x_blk.dtype)
        dx_blk = jnp.einsum("rv,vh->rh", d, w_vocab,
                            preferred_element_type=jnp.float32)
        dw = dw + jnp.einsum("rv,rh->vh", d, x_blk,
                             preferred_element_type=jnp.float32)
        return (loss + nll, dw), dx_blk.astype(x_blk.dtype)

    zero = jnp.zeros((), jnp.float32)
    if not with_grad:
        return lax.scan(block, zero, (x, labels))[0]
    (loss, dw), dx = lax.scan(
        block, (zero, jnp.zeros(w_vocab.shape, jnp.float32)), (x, labels))
    return loss, dx, dw


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _streamed_xent(x, w_vocab, labels, tokens):
    return _xent_row_blocks(x, w_vocab, labels, tokens, with_grad=False)


def _streamed_xent_fwd(x, w_vocab, labels, tokens):
    loss, dx, dw = _xent_row_blocks(x, w_vocab, labels, tokens,
                                    with_grad=True)
    return loss, (dx, dw)


def _streamed_xent_bwd(tokens, grads, g):
    # g is the loss's cotangent: 1 under a plain grad, anything under a
    # scaled or weighted loss.  x and w_vocab share dx's dtype.
    dx, dw = grads
    return (g * dx).astype(dx.dtype), (g * dw).astype(dx.dtype), None


_streamed_xent.defvjp(_streamed_xent_fwd, _streamed_xent_bwd)


def streamed_softmax_xent(x: jax.Array, w_vocab: jax.Array,
                          labels: jax.Array, chunk: int) -> jax.Array:
    """Mean cross-entropy of ``x @ w_vocab.T`` against ``labels`` without
    ever materializing the (tokens, vocab) logits: the tokens are
    flattened to rows and swept in blocks (``_loss_row_block``) over the
    WHOLE vocabulary, so each block's soft-max is complete and its
    gradient is made in the same sweep (``_xent_row_blocks``); a
    differentiated step multiplies by the vocabulary three times, a
    loss-only call once.  This removes all three of the reference's ~4 GB
    fp32 spikes (logits, log-probs, grad-wrt-log-probs, README.md:28-33)
    at once."""
    V, H = w_vocab.shape
    tokens = labels.size
    rows = _loss_row_block(tokens, V, chunk)
    n_blocks = -(-tokens // rows)
    pad = n_blocks * rows - tokens
    x = jnp.pad(x.reshape(tokens, H), ((0, pad), (0, 0)))
    labels = jnp.pad(labels.reshape(tokens), (0, pad), constant_values=-1)
    return _streamed_xent(x.reshape(n_blocks, rows, H),
                          w_vocab.astype(x.dtype),
                          labels.reshape(n_blocks, rows), tokens)


def lm_loss(params: dict, batch, cfg: TransformerConfig,
            *, layer_hook=None, layer_body=None) -> jax.Array:
    """Causal-LM cross-entropy.  ``batch`` = (input_ids, labels) both (B, S),
    the packed-window contract of the reference's TinyStories pipeline
    (``fsdp/utils.py:58-89``: inputs = window[:-1], labels = window[1:]).

    With ``cfg.loss_vocab_chunk`` unset this is the reference-faithful dense
    path: fp32 log-softmax over full (B, S, vocab) logits — the same memory
    spike the reference documents (README.md:28-33).  Set it to stream the
    head instead (see streamed_softmax_xent).
    """
    input_ids, labels = batch
    x, aux = hidden_states(params, input_ids, cfg, layer_hook=layer_hook,
                           layer_body=layer_body, return_aux=True)
    with scope("loss_head"):
        loss = xent_from_hidden(x, _output_embedding(params, cfg), labels,
                                chunk=cfg.loss_vocab_chunk)
    if cfg.n_experts:
        loss = loss + cfg.moe_aux_weight * aux
    return loss


def xent_from_hidden(x: jax.Array, w_vocab: jax.Array, labels: jax.Array,
                     *, chunk: int | None = None) -> jax.Array:
    """Mean causal-LM cross-entropy from final hidden states:
    streamed in row blocks when ``chunk`` is set, dense fp32 otherwise.
    ``w_vocab``: (vocab, H) unembedding rows.  Shared by ``lm_loss`` and
    the pipeline's last stage so the numerics exist once."""
    if chunk:
        return streamed_softmax_xent(x, w_vocab, labels, chunk)
    logits = (x @ w_vocab.T).astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def model_flops_per_token(cfg: TransformerConfig, seq_len: int) -> float:
    require_dense_block(cfg, "utils.flops' dense-block arithmetic")
    from ..utils.flops import get_model_flops_per_token
    return get_model_flops_per_token(cfg, seq_len)
