"""Strategy / collectives: bytes on the wire a step, nccl-tests accounting: every
executed collective's message times its bus factor, from the program's own
ledger of the trace; the chip that moves most."""
from benchmarks.layer_metrics import _collectives

LAYER = "strategy / collectives"
UNIT = "GB/step"
MOVES = "train_tokens_per_s"
RUNNERS = ("train",)


def read(ctx):
    return _collectives.bytes_per_step_gb(ctx)
