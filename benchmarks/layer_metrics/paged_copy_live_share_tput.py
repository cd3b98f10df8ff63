"""Kernels: of the pages the float paged decode kernel copied over the
window's decode steps, the share that held a position its slot could see,
``paged_pages_live / paged_pages_copied`` of the engine's own counters
(both summed over the steps and live slots of the plain bursts, the
second from ``ops/paged_attention.pages_copied``, the function that
states the kernel's copy schedule).  100 where the schedule copies live
pages alone; a schedule that copies whole blocks reads the slots' lengths
against the block (about 65 at 64-640 positions and blocks of 256).  A
program without the counters (the parent of the PR that brought them, or
an engine whose whole-context layers do not run that kernel), or a window
without a decode step, gives nothing."""
LAYER = "kernels"
UNIT = "%"
MOVES = "serve_tokens_per_s"
RUNNERS = ("serve",)


def read(ctx):
    s = ctx.counters["stats"]
    if not s.get("paged_pages_copied"):
        return None
    return 100.0 * s["paged_pages_live"] / s["paged_pages_copied"]
