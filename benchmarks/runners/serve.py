"""Runner ``serve``: the program's ``ServingEngine``, driven through the
facade its ``Fleet`` uses (``start`` / ``enqueue`` / ``step_round`` /
``close_pump``) by the benchmark's own load generator.

One loop serves both kinds of traffic.  An open loop (``poisson``) hands
each request to the engine when it falls due, whatever the engine is doing,
and after the window drains what is left; its requests are timed from when
they were DUE.  A backlog hands everything over at t = 0, stops counting at
the window's end and then drains only what was resident at that moment.

What the traffic file sets is what a deployer must set for that traffic
(``engine``: ``max_batch``, ``max_seq_len``, ``page_size``,
``prefill_chunk``); every other ``ServingEngine`` argument stays at the
program's default, so a PR that improves a default shows.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import harness

IDLE_SLEEP_S = 0.001
#: what this runner calls of the architecture's reference (harness docstring)
REFERENCE_EXPORTS = ("logits_at",)


def init_weights(mcfg, seed: int, scale: float):
    """Weights from the seed in one jitted call, in the dtype they are
    served in.  ``scale`` widens the logits as the program's
    ``serve_bench --param-scale`` does, so that near-ties are common and a
    precision fault changes served tokens."""
    import jax
    from distributed_training_sandbox_tpu.models import transformer as T

    def init(k):
        p = T.init_params(k, mcfg)
        return jax.tree.map(lambda x: (x * scale).astype(x.dtype), p)

    return jax.jit(init)(jax.random.key(seed))


def make_requests(trace, offset_s: float, first_rid: int = 0):
    from distributed_training_sandbox_tpu.serving.scheduler import Request
    return [Request(rid=first_rid + i, prompt=t.prompt,
                    max_new_tokens=t.max_new,
                    arrival_s=offset_s + t.due_s)
            for i, t in enumerate(trace)]


class Driver:
    """The load generator and the round loop, on the engine's clock."""

    def __init__(self, engine, t_engine0: float, span):
        self.engine = engine
        self.t0 = t_engine0
        self.span = span
        self.lateness: list[float] = []
        self.kv_valid_sum = 0
        self.kv_samples = 0
        self.queue_depth: list[tuple[float, int]] = []

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def _round(self, now: float) -> None:
        eng = self.engine
        steps0 = eng.stats["decode_steps"]
        with self.span("bench/round"):
            eng.step_round(now)
        if eng.stats["decode_steps"] > steps0:
            # valid KV the decode steps of this round had to read
            self.kv_valid_sum += sum(
                r.n_prompt + len(r.tokens) for r in eng.batcher.slots
                if r is not None and r.state == "DECODE")
            self.kv_samples += 1
        self.queue_depth.append((now, len(eng.batcher.waiting)))

    def drive(self, reqs, until_s: float, stop_when=None) -> float:
        """Hand over ``reqs`` (sorted by due time) as they fall due and
        run rounds until the engine clock reaches ``until_s``, or
        ``stop_when()`` is true once everything was handed over."""
        eng, i = self.engine, 0
        while True:
            now = self.now()
            while i < len(reqs) and reqs[i].arrival_s <= now:
                eng.enqueue(reqs[i], now)
                self.lateness.append(now - reqs[i].arrival_s)
                i += 1
            if now >= until_s:
                return now
            if i >= len(reqs) and stop_when is not None and stop_when():
                return now
            if eng.batcher.has_work():
                self._round(now)
            else:
                nxt = reqs[i].arrival_s if i < len(reqs) else until_s
                with self.span("bench/idle_wait"):
                    time.sleep(min(max(nxt - now, 0.0), IDLE_SLEEP_S))


def check_against_reference(ref, params, fields, done, seed: int, spec: dict,
                            tol: dict, s_ref: int, n_pos: int) -> dict:
    """Teacher-force a seeded sample of completed requests through the
    plain float32 reference.  The engine exposes tokens, not logits, so the
    distance is the GAP: at every generated position, the reference's
    maximum logit minus the reference's logit of the SERVED token, in units
    of the standard deviation of the reference's logits at that position
    (0 where the served token is the reference's argmax; about 4.4 for a
    token picked at random from this vocabulary).  Its mean over all
    checked tokens and its maximum are held to ``tol``.  Sequences are
    padded at the end to ``s_ref`` and positions to ``n_pos``, so one
    program serves every sample."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gaps(p, ids, pos, toks):
        z = ref.logits_at(p, ids, pos, fields, block=int(spec["block"]))
        served = jnp.take_along_axis(z, toks[:, None], axis=-1)[:, 0]
        return (jnp.max(z, axis=-1) - served) / jnp.std(z, axis=-1)

    rng = np.random.default_rng([int(seed), 0x636865636B])
    pick = rng.permutation(len(done))[:int(spec["requests"])]
    worst, total, n, agree, first = 0.0, 0.0, 0, 0, []
    for j in pick:
        r = done[int(j)]
        toks = np.asarray(r.tokens, np.int32)[:n_pos]
        ids = np.zeros(s_ref, np.int32)
        seq = np.concatenate([r.prompt, toks[:-1]])
        ids[:len(seq)] = seq
        pos = np.full(n_pos, r.n_prompt - 1, np.int32)
        pos[:len(toks)] = r.n_prompt - 1 + np.arange(len(toks))
        tk = np.full(n_pos, toks[0], np.int32)
        tk[:len(toks)] = toks
        g = np.asarray(gaps(params, jnp.asarray(ids), jnp.asarray(pos),
                            jnp.asarray(tk)))[:len(toks)]
        worst = max(worst, float(g.max()))
        total += float(g.sum())
        agree += int((g == 0).sum())
        first.append(float(g[0]))
        n += len(toks)
    out = {"requests_checked": int(len(pick)), "tokens_checked": n,
           "gap_sigma_max": worst,
           "gap_sigma_mean": total / n if n else float("nan"),
           "gap_sigma_first_tokens": first,
           "argmax_agreement": agree / n if n else float("nan")}
    out["ok"] = bool(n > 0 and worst <= float(tol["gap_sigma_max"])
                     and out["gap_sigma_mean"] <= float(tol["gap_sigma_mean"]))
    out["limits"] = {k: tol[k] for k in ("gap_sigma_max", "gap_sigma_mean")}
    return out


def first_token_gate(judged, slo: dict) -> dict:
    """The traffic file's ``slo``: the share of the window's requests whose
    first token came within ``ttft_ms`` of when they were DUE must reach
    ``min_share``.  The time to first token is held as a gate of
    ``correct``, not as a bounded metric: over the hundred requests of a
    window its tail spreads more than a bound may carry, but a change that
    buys its gap between tokens by starving prefill must not pass."""
    got = [1e3 * (r.t_first - r.arrival_s) for r in judged
           if r.t_first is not None]
    share = sum(x <= float(slo["ttft_ms"]) for x in got) / max(len(judged), 1)
    return {"ttft_within_limit_share": share,
            "ttft_ms_p50_p90_max": [harness.percentile(got, q)
                                    for q in (50, 90, 100)],
            "no_first_token": len(judged) - len(got),
            "ttft_gate_ok": bool(share >= float(slo["min_share"]))}


def setup(cell, seed: int, rehearse: bool, span, phases=None):
    """Weights, engine, warm-up: everything before a window.  Returns what
    ``window`` needs; ``benchmarks/sweep.py`` opens several windows on it."""
    import jax
    from distributed_training_sandbox_tpu.serving import ServingEngine

    fields = dict(cell.config["fields"])
    params_t = dict(cell.traffic["params"])
    engine_kw = dict(cell.traffic["engine"])
    if rehearse:
        fields.update(cell.config["rehearse"]["fields"])
        params_t.update(cell.traffic["rehearse"]["params"])
        engine_kw.update(cell.traffic["rehearse"]["engine"])
    mcfg = harness.model_config(fields)
    serve_cfg = cell.config["serve"]
    params = init_weights(mcfg, seed, float(serve_cfg["param_scale"]))
    engine = ServingEngine(params, mcfg, **engine_kw, **serve_cfg["engine"])
    jax.block_until_ready(params)
    if phases:
        phases.mark("weights_and_pool")
    traffic = harness.find_module("traffic", cell.traffic["generator"])
    t_engine0 = time.perf_counter()
    engine.start(t_engine0)
    drv = Driver(engine, t_engine0, span)
    # warm-up: each engine program has one shape, so one request of a
    # single prefill chunk and a single decode burst compiles (or loads)
    # both; serving the cell's own lengths here would be set-up that no
    # measured request needs
    warm = traffic.generate(
        {"arrival": {"process": "backlog", "count": 1},
         "prompt_len": {"dist": "fixed",
                        "value": int(engine_kw["prefill_chunk"])},
         "output_len": {"dist": "fixed", "value": engine.sync_every},
         "max_total": int(engine_kw["max_seq_len"])},
        seed + 1, mcfg.vocab_size, 0.0)
    drv.drive(make_requests(warm, drv.now(), first_rid=-len(warm)),
              until_s=float("inf"),
              stop_when=lambda: not engine.batcher.has_work())
    if phases:
        phases.mark("warm_requests")
    return {"fields": fields, "params_t": params_t, "engine_kw": engine_kw,
            "mcfg": mcfg, "params": params, "engine": engine,
            "traffic": traffic, "drv": drv,
            "devices": jax.devices()[:cell.chips]}


def window(st, window_trace, seconds: float, drain_s: float,
           on_window_end=lambda: None) -> dict:
    """One measured window on a warm engine, then its drain."""
    engine, drv = st["engine"], st["drv"]
    backlog = st["params_t"]["arrival"]["process"] == "backlog"
    stats0 = dict(engine.stats)
    drv.lateness.clear()
    drv.kv_valid_sum = drv.kv_samples = 0
    drv.queue_depth.clear()
    t_open = time.perf_counter()
    offset = t_open - drv.t0
    reqs = make_requests(window_trace, offset)
    with drv.span(harness.WINDOW_SPAN):
        drv.drive(reqs, until_s=offset + seconds)
    stats1 = dict(engine.stats)
    # a backlog's window closes on a round boundary, up to one round late:
    # what was processed is counted, and divided by, as of this moment
    window_actual = drv.now() - offset
    processed = sum(r.n_prompt + len(r.tokens) for r in reqs
                    if r.t_done is not None) + sum(
        r.prefill_pos + len(r.tokens) for r in engine.batcher.slots
        if r is not None)
    lateness = list(drv.lateness)
    kv_valid = (drv.kv_valid_sum, drv.kv_samples)
    queue_depth = [(t - offset, d) for t, d in drv.queue_depth]
    t_window_end = time.perf_counter()
    on_window_end()
    # ---- drain, outside the window
    if backlog:
        judged = [r for r in reqs
                  if r.t_done is not None and r.t_done <= offset + seconds]
        judged += [r for r in engine.batcher.slots if r is not None]
    else:
        judged = reqs
    unfinished = lambda: [r for r in judged if r.t_done is None]  # noqa: E731
    # the deadline counts from here: a traced run has just spent seconds
    # writing its trace, during which the engine did not move
    drv.drive([], until_s=drv.now() + drain_s,
              stop_when=lambda: not unfinished())
    t_close = time.perf_counter()
    failed = len(unfinished()) + sum(
        1 for r in judged if r.t_done is not None
        and len(r.tokens) != r.max_new_tokens)
    records = [{
        "due_s": r.arrival_s - offset,
        "n_prompt": r.n_prompt, "n_tokens": len(r.tokens),
        "t_first_s": None if r.t_first is None else r.t_first - offset,
        "t_done_s": None if r.t_done is None else r.t_done - offset,
        "in_window": r.t_done is not None and r.t_done - offset <= seconds,
    } for r in judged]
    return {
        "t_open": t_open, "t_window_end": t_window_end, "t_close": t_close,
        "judged": judged, "failed": failed,
        "counters": {
            "window_s": seconds, "end_s": t_close - drv.t0 - offset,
            "backlog": backlog, "requests": records,
            "tokens_processed_in_window": processed,
            "window_actual_s": window_actual,
            "lateness_s": lateness,
            "stats": {k: stats1[k] - stats0[k] for k in stats1
                      if k != "peak_pool_util"},
            "engine": {**st["engine_kw"], "n_pages": engine.n_pages},
            "kv_valid_sum": kv_valid[0], "kv_samples": kv_valid[1],
            # both engine programs are ``jit__unknown`` in a trace: their
            # launch counts tell them apart (reduce_trace.alias_modules)
            "program_launches": {
                "decode": stats1["decode_steps"] - stats0["decode_steps"],
                "prefill": stats1["prefill_chunks"]
                - stats0["prefill_chunks"]},
            "queue_depth": queue_depth,
        },
    }


def run(cell, *, ref, seed: int, seconds: float, trace: bool,
        rehearse: bool, watch, phases) -> dict:
    import jax
    st = setup(cell, seed, rehearse, harness.spans(trace), phases)
    params_t, engine = st["params_t"], st["engine"]
    if trace:
        seconds = min(seconds, float(cell.traffic["trace"]["seconds"]))
    if rehearse:
        seconds = min(seconds, 3.0)
    window_trace = st["traffic"].generate(params_t, seed,
                                          st["mcfg"].vocab_size, seconds)
    trace_dir = None
    if trace and not rehearse:
        trace_dir = harness.start_trace()
    w = window(st, window_trace, seconds, float(cell.traffic["drain_s"]),
               on_window_end=(jax.profiler.stop_trace if trace_dir
                              else lambda: None))
    engine.close_pump()
    t_close = time.perf_counter()
    phases.mark("window_and_drain")
    retraces = engine.retraces_after_warmup()
    done = [r for r in w["judged"] if r.t_done is not None]

    # ---- correctness, outside the window
    olen = params_t["output_len"]
    check = check_against_reference(
        ref, st["params"], st["fields"], done, seed,
        cell.traffic["check"] if not rehearse
        else cell.traffic["rehearse"]["check"],
        cell.check, s_ref=int(params_t["max_total"]),
        n_pos=int(olen.get("max", olen.get("value")))) \
        if done else {"ok": False, "requests_checked": 0}
    check["retraces_after_warmup"] = retraces
    slo = None if rehearse else cell.traffic.get("slo")
    if slo:
        check.update(first_token_gate(w["judged"], slo))
    # each number compared beside its limit, flat; a share has a least
    check["compared"] = {
        "requests_checked_min": [check["requests_checked"], 1],
        **{k: [check[k], v] for k, v in check.get("limits", {}).items()},
        "retraces_after_warmup": [retraces, 0],
        **({"ttft_within_limit_share_min": [check["ttft_within_limit_share"],
                                            float(slo["min_share"])]}
           if slo else {})}
    return {
        "attempted": len(w["judged"]), "failed": w["failed"],
        "correct": bool(check["ok"] and w["failed"] == 0
                        and retraces == 0
                        and check.get("ttft_gate_ok", True)),
        "check": check,
        "window_wall_s": t_close - w["t_open"],
        "compiles_in_window": watch.inside(w["t_open"], w["t_window_end"]),
        "trace_dir": trace_dir,
        "devices": st["devices"],
        "fields": st["fields"],
        "counters": w["counters"],
    }
