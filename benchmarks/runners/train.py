"""Runner ``train``: the program's explicit-FSDP training loop, fed by the
benchmark's own traffic.

Composed in the order ``scripts/train_fsdp.py:_leg`` composes it —
``DevicePrefetcher`` -> ``make_fsdp_train_step`` -> ``StepPump.emit`` — with
every knob of the three at the program's default, so a PR that improves a
default shows.  What the configuration file sets is what a deployer must
set: the model's fields and the mesh.  The runner never names a preset.

The window is a fixed amount of work: after warm-up the runner times two
blocked steps, dispatches ``ceil(seconds / step time)`` whole steps through
the pump, and closes the window when the last loss is ready.

``correct`` holds two things to the plain reference, outside the window.
The model: ``lm_loss`` and its gradient norms per group on one sequence's
first positions.  And the program that is measured: the loss the real step
returns for the stream's first batch on the initial weights agrees with the
reference's float32 loss of that whole batch, and the same batch fed once
more, after one update, loses what the configuration's ``step_drop`` band
says one AdamW update takes off it (the step returns nothing but its loss,
so the update is held by what it does to the loss).
"""

from __future__ import annotations

import itertools
import math
import time

from benchmarks import harness
from benchmarks.reference import dense_gqa as ref

WARM_STEPS = 3        # one that compiles, then two that are timed


def init_sharded(mcfg, mesh, seed: int):
    """Weights from the seed in ONE jitted call, born in their at-rest FSDP
    sharding (``fsdp_specs``), in the dtype they are trained in."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from distributed_training_sandbox_tpu.models import transformer as T
    from distributed_training_sandbox_tpu.parallel import fsdp
    init = lambda k: T.init_params(k, mcfg)  # noqa: E731
    shapes = jax.eval_shape(init, jax.random.key(0))
    specs = fsdp.fsdp_specs(shapes)
    fsdp.check_divisibility(shapes, specs, mesh)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P))
    params = jax.jit(init, out_shardings=shardings)(jax.random.key(seed))
    return params, shardings


def system_loss_and_norms(params, mcfg, mesh, ids, labels):
    """The system's ``lm_loss`` on one sequence, and its gradient norms per
    group, with the parameters sharded as they rest.  The splash kernel
    cannot be partitioned automatically, so this runs under ``shard_map``
    through the model's own ``layer_hook`` seam: each leaf is gathered where
    it is used, every chip computes the same sequence, and the gathers'
    transposes sum the chips' equal gradients into the shards (hence the
    division by the mesh size)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from distributed_training_sandbox_tpu.models import transformer as T
    from distributed_training_sandbox_tpu.parallel import fsdp
    is_spec = lambda x: isinstance(x, P)  # noqa: E731
    specs = fsdp.fsdp_specs(params)
    ws = int(mesh.shape["dp"])

    def gather(x, spec):
        for dim, name in enumerate(spec):
            if name == "dp":
                return jax.lax.all_gather(x, "dp", axis=dim, tiled=True)
        return x

    in_layer = jax.tree.map(lambda s: P(*s[1:]), specs["layers"],
                            is_leaf=is_spec)

    def hook(layer):
        return jax.tree.map(gather, layer, in_layer, is_leaf=is_spec)

    def body(shards, ids, labels):
        def loss_fn(sh):
            outer = {k: gather(v, specs[k]) for k, v in sh.items()
                     if k != "layers"}
            return T.lm_loss({**outer, "layers": sh["layers"]},
                             (ids[None], labels[None]), mcfg,
                             layer_hook=hook)
        loss, grads = jax.value_and_grad(loss_fn)(shards)
        sumsq = ref.group_sumsq(jax.tree.map(
            lambda g: g.astype(jnp.float32) / ws, grads))
        return loss, jax.tree.map(jnp.sqrt, jax.lax.psum(sumsq, "dp"))

    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(specs, P(), P()),
                              out_specs=(P(), P()), check_vma=False))
    return f(params, ids, labels)


def reference_loss_and_norms(params, shardings, fields, ids, labels):
    """The plain float32 reference under ``jit`` with the parameters
    sharded as they rest and its gradients held to the same sharding, so
    that it fits beside the cell's state."""
    import jax

    def f(p, ids, labels):
        loss, grads = jax.value_and_grad(
            lambda q: ref.loss(q, ids, labels, fields))(p)
        grads = jax.lax.with_sharding_constraint(grads, shardings)
        return loss, ref.group_norms(grads)

    return jax.jit(f)(params, ids, labels)


def check_against_reference(params, shardings, mcfg, mesh, fields, ids,
                            labels, tol: dict) -> dict:
    """The system's loss and per-group gradient norms on one seeded
    sequence against the reference's, within the configuration's stated
    tolerances."""
    sys_loss, sys_norms = system_loss_and_norms(params, mcfg, mesh, ids,
                                                labels)
    ref_loss, ref_norms = reference_loss_and_norms(params, shardings,
                                                   fields, ids, labels)
    sys_loss, ref_loss = float(sys_loss), float(ref_loss)
    rel = {g: abs(float(sys_norms[g]) - float(ref_norms[g]))
           / float(ref_norms[g]) for g in ref_norms}
    out = {"loss_system": sys_loss, "loss_reference": ref_loss,
           "loss_abs_diff": abs(sys_loss - ref_loss),
           "grad_norm_rel_diff": rel}
    out["ok"] = bool(
        math.isfinite(sys_loss)
        and out["loss_abs_diff"] <= float(tol["loss_abs"])
        and all(math.isfinite(v) and v <= float(tol["grad_norm_rel"][g])
                for g, v in rel.items()))
    return out


def reference_batch_loss(params, fields, batch, block: int) -> float:
    """The reference's float32 loss of a whole batch (the mean over its
    sequences, as the step's own mean over chips and sequences), under
    ``jit`` with the parameters sharded as they rest and the batch sharded
    as the prefetcher shards it."""
    import jax
    import jax.numpy as jnp

    def f(p, ids, labels):
        one = lambda i, l: ref.loss(p, i, l, fields, block=block)  # noqa: E731
        return jnp.mean(jax.vmap(one)(ids, labels))

    return float(jax.jit(f)(params, *batch))


def check_step(loss0: float, loss1: float, ref0: float, tol: dict,
               band) -> dict:
    """The real step against the reference: ``loss0`` (first batch, initial
    weights) within ``loss_abs`` of the reference's loss of that batch, and
    ``loss0 - loss1`` (the same batch again after one update) inside
    ``band`` = [least, most], where the configuration states one."""
    out = {"step_loss": loss0, "step_loss_reference": ref0,
           "step_loss_abs_diff": abs(loss0 - ref0),
           "step_loss_after_update": loss1, "step_drop": loss0 - loss1}
    out["step_ok"] = bool(
        math.isfinite(loss0) and math.isfinite(loss1)
        and out["step_loss_abs_diff"] <= float(tol["loss_abs"])
        and (band is None
             or float(band[0]) <= out["step_drop"] <= float(band[1])))
    return out


def run(cell, *, seed: int, seconds: float, trace: bool, rehearse: bool,
        watch, phases) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from distributed_training_sandbox_tpu.parallel import fsdp
    from distributed_training_sandbox_tpu.runtime import (
        DevicePrefetcher, StepPump)
    from distributed_training_sandbox_tpu.utils import make_mesh

    fields = dict(cell.config["fields"])
    params_t = dict(cell.traffic["params"])
    if rehearse:
        fields.update(cell.config["rehearse"]["fields"])
        params_t.update(cell.traffic["rehearse"]["params"])
    mcfg = harness.model_config(fields)
    devices = jax.devices()[:cell.chips]
    mesh = make_mesh(dict(cell.config["train"]["mesh"]), devices=devices,
                     register=False)
    if math.prod(mesh.shape.values()) != cell.chips:
        raise harness.BenchmarkError(
            f"config mesh {dict(mesh.shape)} is not the cell's "
            f"{cell.chips} chip(s)")

    params, shardings = init_sharded(mcfg, mesh, seed)
    opt_state = fsdp.init_fsdp_opt_state(params)
    step = fsdp.make_fsdp_train_step(params, mcfg, mesh)
    jax.block_until_ready(params)
    phases.mark("weights")

    traffic = harness.find_module("traffic", cell.traffic["generator"])
    seq_len, gb = int(params_t["seq_len"]), int(params_t["global_batch"])
    tokens_per_step = seq_len * gb

    # ---- correctness, outside the window, on the weights as initialised:
    # the first positions of the stream's first sequence, then the
    # reference's loss of the whole first batch, for the real step
    tol = cell.config["check"]
    batch0 = next(traffic.batches(params_t, seed, mcfg.vocab_size))
    ids, labels = batch0
    n_check = int(cell.traffic["check"]["positions"]
                  if not rehearse else min(seq_len, 128))
    check = check_against_reference(
        params, shardings, mcfg, mesh, fields, jnp.asarray(ids[0, :n_check]),
        jnp.asarray(labels[0, :n_check]), tol)
    ref0 = reference_batch_loss(
        params, fields,
        jax.device_put(batch0, NamedSharding(mesh, P("dp"))),
        block=n_check)
    phases.mark("reference_check")

    span = harness.spans(trace)
    waits: list[float] = []

    def loop(pref, pump, n):
        nonlocal params, opt_state
        for _ in range(n):
            t0 = time.perf_counter()
            with span("bench/prefetch_wait"):
                batch = next(pref)
            waits.append(time.perf_counter() - t0)
            with span("bench/dispatch"):
                params, opt_state, loss = step(params, opt_state, batch)
            with span("bench/pump_emit"):
                pump.emit(loss, tokens=tokens_per_step)

    # the stream, with its first batch fed twice: warm-up step 0 gives the
    # loss the reference is held against, step 1 the same batch's loss
    # after one update
    stream = itertools.chain(
        [batch0], traffic.batches(params_t, seed, mcfg.vocab_size))
    with DevicePrefetcher(stream, mesh=mesh, spec=P("dp")) as pref:
        # ---- warm-up: the one shape this cell uses
        with StepPump() as pump:
            loop(pref, pump, 1)
        loss0 = pump.losses[0]
        phases.mark("first_step")
        t0 = time.perf_counter()
        with StepPump() as pump:
            loop(pref, pump, WARM_STEPS - 1)
        step_s = (time.perf_counter() - t0) / (WARM_STEPS - 1)
        check.update(check_step(
            loss0, pump.losses[0], ref0, tol,
            None if rehearse else tol.get("step_drop")))
        check["ok"] = bool(check["ok"] and check["step_ok"])

        if rehearse:
            n_steps = 3
        elif trace:
            n_steps = int(cell.traffic["trace"]["steps"])
        else:
            n_steps = max(int(math.ceil(seconds / step_s)), 2)
        waits.clear()
        trace_dir = harness.start_trace() if trace and not rehearse else None
        phases.mark("warm_steps")
        # ---- the window
        t_open = time.perf_counter()
        with span(harness.WINDOW_SPAN):
            with StepPump() as pump:
                loop(pref, pump, n_steps)
                with span("bench/pump_drain"):
                    pump.close()
        t_close = time.perf_counter()
        phases.mark("window")
        if trace_dir is not None:
            jax.profiler.stop_trace()

    losses = pump.losses
    failed = sum(1 for x in losses if not math.isfinite(x)) \
        + (n_steps - len(losses))
    elapsed = t_close - t_open
    return {
        "attempted": n_steps, "failed": failed,
        "correct": check["ok"] and failed == 0,
        "check": check,
        "window_wall_s": elapsed,
        "compiles_in_window": watch.inside(t_open, t_close),
        "trace_dir": trace_dir,
        "devices": devices,
        "fields": fields,
        "counters": {
            "steps": n_steps, "tokens": n_steps * tokens_per_step,
            "elapsed_s": elapsed, "seq_len": seq_len, "global_batch": gb,
            "losses": losses, "prefetch_wait_s": list(waits),
        },
    }
