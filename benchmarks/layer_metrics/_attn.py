"""Shared by the attention-kernel readers: which trace events are the
splash-attention Mosaic kernels.  On a v5e under jax 0.9 a Pallas call is
one event named after its kernel function (``splash_mha_fwd...``,
``splash_mha_dq...``, ``splash_mha_dkv...``); older stacks name it
``custom-call`` / ``tpu_custom_call``."""
KERNEL = r"(splash|mha_|flash_attention|tpu_custom_call|^custom-call)"


def kernel_seconds_per_step(ctx):
    ns = ctx.trace.group_ns(KERNEL)
    return ns / 1e9 / ctx.counters["steps"] if ns else None
