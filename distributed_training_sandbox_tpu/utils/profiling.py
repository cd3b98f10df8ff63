"""XLA profiler harness with torch.profiler-style schedule semantics.

The reference wraps every hot loop in ``torch.profiler.profile`` with a
``schedule(skip_first, wait, warmup, active, repeat)`` and
``tensorboard_trace_handler`` (``DDP/ddp.py:128-151``,
``fsdp/train_fsdp.py:106-138``), calling ``profiler.step()`` each iteration and
marking phases with ``record_function``.  The TPU twin drives
``jax.profiler.start_trace / stop_trace`` from the same schedule state machine
(warmup steps are traced too — they are how you *see* warmup in the timeline),
writes TensorBoard/perfetto-compatible traces into the same ``TRACE_DIR``
contract.  Phases are marked in two places: host spans through
``telemetry.spans.maybe_span`` (a ``jax.profiler.TraceAnnotation``, so they
share the device events' clock) and device ops through :func:`scope`
(``jax.named_scope``) with the names of :data:`SCOPES`.
"""

from __future__ import annotations

import os
from dataclasses import dataclass

import jax


@dataclass(frozen=True)
class ProfileSchedule:
    """skip_first → (wait → warmup+active)×repeat, as in torch.profiler.

    The reference's DDP/zero schedule: skip_first=5, wait=1, warmup=2,
    active=5, repeat=1 (``DDP/ddp.py:132-138``); fsdp uses wait=5, warmup=5,
    active=10 (``fsdp/train_fsdp.py:124-137``).
    """
    skip_first: int = 5
    wait: int = 1
    warmup: int = 2
    active: int = 5
    repeat: int = 1

    def phase(self, step: int) -> str:
        """Phase for 0-based step index: 'skip' | 'wait' | 'trace' | 'done'."""
        if step < self.skip_first:
            return "skip"
        s = step - self.skip_first
        cycle = self.wait + self.warmup + self.active
        if self.repeat and s >= cycle * self.repeat:
            return "done"
        pos = s % cycle
        return "wait" if pos < self.wait else "trace"


def default_trace_dir() -> str:
    """TRACE_DIR env contract (reference ``modal_utils.py`` / ``zero1.py:210``:
    launcher exports TRACE_DIR, scripts default to ./profiler_traces)."""
    return os.environ.get("TRACE_DIR",
                          os.environ.get("DDP_TRACE_DIR", "./profiler_traces"))


class Profiler:
    """Schedule-driven jax.profiler session.  Call ``step()`` once per
    training step (the reference calls ``profiler.step()`` inside the
    optimizer-step block, ``DDP/ddp.py:172-173``)."""

    def __init__(self, trace_dir: str | None = None,
                 schedule: ProfileSchedule | None = None,
                 enabled: bool | None = None):
        self.trace_dir = trace_dir or default_trace_dir()
        self.schedule = schedule or ProfileSchedule()
        # rank-0-only tracing, as in every reference script
        self.enabled = (jax.process_index() == 0) if enabled is None else enabled
        self._step = 0
        self._tracing = False
        # session directories (plugins/profile/<ts>/) THIS profiler
        # created, newest last — recorded by diffing the dir around each
        # start/stop pair so trace analysis can target exactly the
        # session it owns instead of "newest file anywhere by mtime"
        self.owned_sessions: list[str] = []
        self._pre_sessions: set[str] = set()

    def _sessions(self) -> set[str]:
        from .trace_analysis import profile_session_dirs
        return set(profile_session_dirs(self.trace_dir))

    def _record_owned(self) -> None:
        new = sorted(self._sessions() - self._pre_sessions)
        self.owned_sessions.extend(
            s for s in new if s not in self.owned_sessions)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    def pending_transition(self) -> bool:
        """True iff the NEXT ``step()`` call will start or stop a trace.
        The async step pump barriers exactly there, so traces bound the
        intended steps even with work in flight."""
        if not self.enabled:
            return False
        return (self.schedule.phase(self._step + 1) == "trace") \
            != self._tracing

    def step(self) -> None:
        if not self.enabled:
            return
        self._step += 1
        phase = self.schedule.phase(self._step)  # phase of the *next* step
        if phase == "trace" and not self._tracing:
            os.makedirs(self.trace_dir, exist_ok=True)
            self._pre_sessions = self._sessions()
            jax.profiler.start_trace(self.trace_dir)
            self._tracing = True
        elif phase in ("wait", "done", "skip") and self._tracing:
            jax.profiler.stop_trace()
            self._tracing = False
            self._record_owned()

    def stop(self) -> None:
        if self._tracing:
            jax.profiler.stop_trace()
            self._tracing = False
            self._record_owned()


#: The closed catalogue of device scope names a trace reader attributes op
#: time to.  An op's ``op_name`` metadata is the path of the scopes open
#: when it was traced (``jit(step)/forward_backward/transpose(jvp(mlp))/
#: dot_general``); a reader takes the INNERMOST catalogue name anywhere in
#: that path, so the backward pass and the remat re-run of a layer count
#: under the layer's own names.  ``benchmarks/layer_metrics/_scopes.py``
#: holds a copy, pinned to this tuple by a test.  Containers that only group
#: other scopes (``forward_backward``) are deliberately not in it: what runs
#: under them and under no name below is the tracing's own blind spot.
SCOPES = (
    # the shared model (models/transformer.py), training and serving alike
    "embed",       # token lookup and the RoPE tables
    "attn_qkv",    # pre-attention norm, q/k/v projections, RoPE
    "attn_core",   # splash / XLA / ring attention; the engine's scores,
                   # softmax and PV over the gathered view
    "attn_out",    # output projection
    "mlp",         # both MLP norms + SwiGLU, or an expert layer: SUBSCOPES
    "loss_head",   # final norm, unembedding, (streamed) cross-entropy
    # the serving engine's programs (serving/engine.py)
    "kv_write",    # scatter of the new K/V rows into their pages
    "kv_gather",   # gather of a slot's pages into the contiguous view
    "sample",      # final norm + unembedding at one position + argmax;
                   # a prefill chunk's sits in a ``cond`` and runs only
                   # when the chunk ends a prompt
    # the FSDP strategy step (parallel/fsdp.py)
    "fsdp_layer_gather", "fsdp_root_gather", "fsdp_pre_gather_layers",
    "loss_mean", "grad_mean",
    "opt_step",    # the optimizer update
)

#: A declared second level beneath ``mlp``, opened by the latent block's
#: expert layers (``models/mla_moe.py``).  A reader of ``SCOPES`` still books
#: these ops to ``mlp`` (the innermost CATALOGUE name); a reader of this
#: tuple splits ``mlp`` by them (``benchmarks/layer_metrics/_subscopes.py``
#: holds a copy, pinned by a test).  ``parallel/expert.py``'s older
#: ``moe_*`` scopes are its own and are read by nothing.
SUBSCOPES = (
    "moe_route",    # router matmul, sigmoid, top-k, weights, the counters
    "moe_experts",  # held experts: the plan of visits over the (row,
                    # expert) pairs and the grouped product's one call
    "moe_shared",   # the shared expert
)

#: A declared second level of the linear layers of the blocks with state
#: slots (``models/gdn_hybrid.py`` and, since PR 40, the Mamba-2 layers of
#: ``models/ssm_moe.py``; ``serving/engine._paged_block_forward``):
#: ``lin_conv`` beneath ``attn_qkv``, ``lin_scan`` and ``lin_step`` beneath
#: ``attn_core``.  A reader of ``SCOPES`` books these ops to the catalogue
#: name above them; ``benchmarks/layer_metrics/_linscopes.py`` holds a copy
#: of this tuple, pinned by a test, and splits them out.
LINEAR_SUBSCOPES = (
    "lin_conv",   # q/k/v projections' depthwise causal conv, the tail update
    "lin_scan",   # a prefill chunk's chunked scan from the carried state
    "lin_step",   # a decode step's recurrence on every slot's state
)


#: A declared second level beneath ``attn_core`` round the paged attention
#: of the full-attention layers of the hybrid with expert layers
#: (``models/gdn_moe.py``; ``serving/engine._paged_attend`` opens it where
#: that block's forward asks, in a decode step and in a prefill chunk, so
#: the other blocks' programs keep their op names): the paged kernels'
#: calls apart from the linear layers' step or scan, which share
#: ``attn_core``.  ``benchmarks/layer_metrics/_attnscopes.py`` holds a copy
#: of this tuple, pinned by a test.
ATTENTION_SUBSCOPES = (
    "attn_paged",  # the paged decode / flash prefill kernel's call (or,
                   # off the chip, the gather path's scores, softmax and PV)
)


#: A second name beneath ``attn_core``, round the paged attention of the
#: WINDOW layers of the block that mixes them with full-attention layers
#: (``models/swa_moe.py``; ``serving/engine._paged_block_forward`` asks
#: :func:`_paged_attend` for it, in a decode step and in a prefill chunk,
#: after ``kv_write``), whose full layers open ``attn_paged`` above: the
#: two kinds of layer read different rows of a request and are read apart.
#: A tuple of its own, since tests pin the older ones;
#: ``benchmarks/layer_metrics/_winscopes.py`` holds a copy, pinned by a
#: test.
WINDOW_SUBSCOPES = (
    "attn_window",  # the paged decode / flash prefill kernel's call over a
                    # ring's ordered view with a lower bound (or, off the
                    # chip, the gather path's scores, softmax and PV)
)


#: A declared second level beneath ``attn_qkv`` in the block of compressed
#: convolutional attention (``models/cca_moe.py``, whose ``attention_qkv``
#: opens it, in a decode step and in a prefill chunk): what stands between
#: the latents' projection and the heads' norms.  A tuple of its own, since
#: tests pin the older ones; ``benchmarks/layer_metrics/_ccascopes.py``
#: holds a copy, pinned by a test.
CCA_SUBSCOPES = (
    "cca_conv",  # both causal convolutions over the latents continued from
                 # the slot's tail, the q-k mean, the value shift, the
                 # tail's update
)


#: A declared second level beneath ``sample`` in the looped dense block
#: (``models/loop_dense.py``; ``serving/engine._paged_block_forward`` opens
#: it at the end of every pass and once after the last, in a decode step and
#: in a prefill chunk): the model's final norm where a pass ends, the exit
#: gate, the exit distribution and the choice of the one state a row that
#: the head reads.  A reader of ``SCOPES`` books these ops to ``sample``
#: (final norm + unembedding + argmax); ``benchmarks/layer_metrics/
#: _loopscopes.py`` holds a copy of this tuple, pinned by a test, and reads
#: them apart.
LOOP_SUBSCOPES = (
    "loop_gate",  # pass-end final norm, sigmoid gate, cumulated exit
                  # probability, the running choice of h_e, the counters
)


def scope(name: str):
    """Device-side marker for code *inside* jit: prefixes XLA op names so
    collectives/matmuls attribute to the phase in the trace.  Writes
    ``op_name`` metadata only: the compiled program does not change."""
    return jax.named_scope(name)
