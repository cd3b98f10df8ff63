"""TPU-native distributed-training sandbox.

A ground-up JAX / XLA / shard_map / Pallas framework with the capabilities of
the reference `xo-toybox/distributed-training-sandbox` (CUDA/NCCL/torch):
from-scratch, trace-first implementations of DDP, ZeRO-1/2/3, fully-sharded
training of a real transformer, GPipe/1F1B pipeline schedules, and a
low-precision benchmark sweep — each replaying the reference's collective
choreography over a named TPU mesh, instrumented with the XLA profiler.

Layer map (SURVEY.md §1):
  L1 comm backend  -> ops.collectives (lax.psum / all_gather / psum_scatter /
                      ppermute over a named Mesh; ICI/DCN in place of NCCL)
  L2 shared utils  -> utils.{mesh,prng,memory,tracker,flops,profiling,config}
  L3 strategies    -> parallel.{ddp,zero1,zero2,zero3,fsdp,pipeline} + scripts/
  L4 launch        -> launch.launcher (config-driven, run-id'd trace dirs)
"""

__version__ = "0.2.0"

from . import utils, ops  # noqa: F401
# `launch` is importable as a subpackage (`from distributed_training_sandbox_tpu
# import launch`) but not imported eagerly: it is pure stdlib and must stay
# importable before jax backend initialization.

# every entry point imports this package before it compiles anything
utils.configure_compile_cache()
