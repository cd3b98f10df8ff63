"""Model step: the mean 1-based pass whose state reached the head, over the
rows the window's decode steps sampled: ``exit_step_sum`` over the sampled
rows (``ut_passes / total_ut_steps``) of the engine's own counters, summed
on the device.  ``total_ut_steps`` (4.0) at the published
``early_exit_threshold`` of 1; less means some passes' states reached the
head early.  Every pass of every row runs whatever it reads: the gate
chooses the head's input and saves no compute, so this describes the MODEL
(its gate and its threshold) and no change to the program should move it.
``better`` must name a direction: higher, the published behaviour.  A
program without the counters gives nothing."""
LAYER = "model step"
UNIT = "passes"
MOVES = "serve_tokens_per_s"
RUNNERS = ("serve",)


def read(ctx):
    s = ctx.counters["stats"]
    if not s.get("ut_passes"):
        return None
    rows = s["ut_passes"] / int(ctx.fields["total_ut_steps"])
    return s["exit_step_sum"] / rows
