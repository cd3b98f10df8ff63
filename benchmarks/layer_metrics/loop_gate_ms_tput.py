"""Model step: device self time of the decode program's ``loop_gate``
subscope per launch: the model's final norm where each pass ends, the exit
gate, the cumulated exit probability and the running choice of the one
state a row that the head reads, all ``total_ut_steps`` passes of one decode
step (the looped dense block).  A chain of small ops on ``max_batch`` rows,
so it says what the seam between passes costs beside the layers.  A program
that opens no such scope gives nothing."""
from benchmarks.layer_metrics import _loopscopes

LAYER = "model step"
UNIT = "ms"
MOVES = "serve_tokens_per_s"
RUNNERS = ("serve",)


def read(ctx):
    return _loopscopes.subscope_ms_per_launch(ctx, ("loop_gate",), "decode")
