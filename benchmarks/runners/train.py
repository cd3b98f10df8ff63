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

``correct`` holds the program that is measured to the plain reference,
outside the window.  The loss the real step returns for the stream's first
batch on the initial weights agrees with the reference's float32 loss of
that whole batch, and the same batch fed once more, after one update, loses
what the ``step_drop`` band says one AdamW update takes off it.  The
gradient, per parameter group, one of two ways, as the cell's ``check``
says:

* ``"gradient": "step"``: the first gradient as the optimizer got it in the
  timed step itself, at the cell's own batch and length.  Adam's first
  moment after one update from zeros is ``(1 - b1)`` times that gradient,
  so its norms are read from the step's state; the reference's are those
  of its loss of the whole batch, taken ``block`` rows at a time.  With
  it, the norms of the change that first update made to the parameters,
  against one plain Adam update from the reference's gradient.
* otherwise (``positions``): ``lm_loss`` and its gradient in a pass of
  their own on the first ``positions`` of one sequence.

A rehearsal merges the data files' ``rehearse.check`` over the tolerances.
"""

from __future__ import annotations

import inspect
import itertools
import math
import time

from benchmarks import harness

WARM_STEPS = 3        # one that compiles, then two that are timed
#: what this runner calls of the architecture's reference (harness docstring)
REFERENCE_EXPORTS = ("loss", "group_sumsq", "group_norms")


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


def system_loss_and_norms(ref, params, mcfg, mesh, ids, labels):
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


def reference_loss_and_norms(ref, params, shardings, fields, ids, labels):
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


def check_against_reference(ref, params, shardings, mcfg, mesh, fields,
                            ids, labels, tol: dict) -> dict:
    """The system's loss and per-group gradient norms on one seeded
    sequence against the reference's, within the configuration's stated
    tolerances."""
    sys_loss, sys_norms = system_loss_and_norms(ref, params, mcfg, mesh, ids,
                                                labels)
    ref_loss, ref_norms = reference_loss_and_norms(ref, params, shardings,
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


def reference_batch_loss(ref, params, fields, batch, block: int) -> float:
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


def reference_first_update(ref, params, shardings, fields, batch, block: int,
                           lr: float, eps: float):
    """``reference_batch_loss`` with, per group, the norm of that loss's
    gradient (held to the parameters' sharding) and the norm of the change
    one plain Adam update from a zero state makes to the parameters as they
    are stored: bias-corrected moments ``g`` and ``g * g``, so each weight
    moves by ``lr * g / (|g| + eps)`` and is rounded to its own dtype."""
    import jax
    import jax.numpy as jnp

    def change(p, g):
        # reduce_precision, not a cast there and back, which XLA may drop
        g, was, fi = g.astype(jnp.float32), p.astype(jnp.float32), \
            jnp.finfo(p.dtype)
        return jax.lax.reduce_precision(
            was - lr * g / (jnp.abs(g) + eps), fi.nexp, fi.nmant) - was

    def f(p, ids, labels):
        def mean_loss(q):
            one = lambda i, l: ref.loss(q, i, l, fields, block=block)  # noqa: E731
            return jnp.mean(jax.vmap(one)(ids, labels))

        loss, grads = jax.value_and_grad(mean_loss)(p)
        grads = jax.lax.with_sharding_constraint(grads, shardings)
        return loss, ref.group_norms(grads), ref.group_norms(
            jax.tree.map(change, p, grads))

    loss, *norms = jax.jit(f)(params, *batch)
    return (float(loss),
            *({g: float(v) for g, v in n.items()} for n in norms))


def check_first_update(ref, before, after, mu, b1: float, ref_grad: dict,
                       ref_change: dict, tol: dict) -> dict:
    """The timed step's first update against the reference's, per group:
    the gradient its optimizer got, from Adam's first moment after it
    (``mu = (1 - b1) x gradient``, from zeros, with ``1 - b1`` as the
    moment's own dtype holds it: 0.1 is 0.1001 in bfloat16), and the change
    it made to the parameters."""
    import jax
    import jax.numpy as jnp
    grad = jax.jit(ref.group_norms)(mu)
    kept = float(jnp.asarray(1.0 - b1, jax.tree.leaves(mu)[0].dtype))
    moved = jax.jit(lambda a, b: ref.group_norms(jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b))
    )(after, before)
    # a group that one update cannot move as it is stored (bf16 norm
    # weights at 1.0) has a reference norm of 0: the gap is then absolute
    rel = lambda have, want: abs(have - want) / (want or 1.0)  # noqa: E731
    out = {"gradient": "step",
           "grad_norm_reference": ref_grad, "update_norm_reference": ref_change,
           "grad_norm_rel_diff": {g: rel(float(grad[g]) / kept, w)
                                  for g, w in ref_grad.items()},
           "update_norm_rel_diff": {g: rel(float(moved[g]), w)
                                    for g, w in ref_change.items()}}
    out["ok"] = all(
        math.isfinite(v) and v <= float(tol[key][g])
        for key in ("grad_norm_rel", "update_norm_rel")
        for g, v in out[key + "_diff"].items())
    return out


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


def compared(check: dict) -> dict:
    """Each number the check compared beside its limit, flat:
    ``{name: [number, limit]}``; the drop ``[number, least, most]``."""
    lim = check["limits"]
    out = {"loss_abs": [check["step_loss_abs_diff"], lim["loss_abs"]]}
    if "loss_abs_diff" in check:        # the pass on ``positions``
        out["loss_abs.positions"] = [check["loss_abs_diff"], lim["loss_abs"]]
    for key in ("grad_norm_rel", "update_norm_rel"):
        for group, gap in check.get(key + "_diff", {}).items():
            out[f"{key}.{group}"] = [gap, lim[key][group]]
    if lim.get("step_drop"):
        out["step_drop"] = [check["step_drop"], *lim["step_drop"]]
    return out


def run(cell, *, ref, seed: int, seconds: float, trace: bool,
        rehearse: bool, watch, phases) -> dict:
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
    jax.block_until_ready(params)
    phases.mark("weights")

    traffic = harness.find_module("traffic", cell.traffic["generator"])
    seq_len, gb = int(params_t["seq_len"]), int(params_t["global_batch"])
    tokens_per_step = seq_len * gb

    # ---- correctness, outside the window, on the weights as initialised
    # and before the optimizer's state is made: the reference's loss of the
    # stream's whole first batch, for the real step, and its gradient norms
    tol = cell.tolerances(rehearse)
    from_step = tol.get("gradient") == "step"
    batch0 = next(traffic.batches(params_t, seed, mcfg.vocab_size))
    ids, labels = batch0
    on_mesh = jax.device_put(batch0, NamedSharding(mesh, P("dp")))
    adam = {k: v.default for k, v in inspect.signature(
        fsdp.make_fsdp_train_step).parameters.items()
        if k in ("lr", "b1", "eps")}     # the program's own defaults
    if from_step:
        ref0, ref_grad, ref_change = reference_first_update(
            ref, params, shardings, fields, on_mesh, int(tol["block"]),
            adam["lr"], adam["eps"])
    else:
        n_check = min(seq_len, 128) if rehearse else int(tol["positions"])
        check = check_against_reference(
            ref, params, shardings, mcfg, mesh, fields,
            jnp.asarray(ids[0, :n_check]), jnp.asarray(labels[0, :n_check]),
            tol)
        ref0 = reference_batch_loss(ref, params, fields, on_mesh,
                                    block=n_check)
    phases.mark("reference_check")
    opt_state = fsdp.init_fsdp_opt_state(params)
    step = fsdp.make_fsdp_train_step(params, mcfg, mesh)

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
        before = jax.tree.map(jnp.copy, params) if from_step else None
        with StepPump() as pump:
            loop(pref, pump, 1)
        loss0 = pump.losses[0]
        if from_step:
            check = check_first_update(ref, before, params, opt_state.mu,
                                       adam["b1"], ref_grad, ref_change, tol)
            del before
        phases.mark("first_step")
        t0 = time.perf_counter()
        with StepPump() as pump:
            loop(pref, pump, WARM_STEPS - 1)
        step_s = (time.perf_counter() - t0) / (WARM_STEPS - 1)
        check.update(check_step(loss0, pump.losses[0], ref0, tol,
                                tol.get("step_drop")))
        check["ok"] = bool(check["ok"] and check["step_ok"])
        check["limits"] = {k: tol[k] for k in
                           ("loss_abs", "grad_norm_rel", "update_norm_rel",
                            "step_drop")
                           if k in tol}
        check["compared"] = compared(check)

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
