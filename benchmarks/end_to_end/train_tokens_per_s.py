"""Global tokens of the window's whole steps over the time from the first
measured step's dispatch to ``block_until_ready`` of the last one's loss."""
UNIT = "tokens/s"
RUNNERS = ("train",)


def read(ctx):
    c = ctx.counters
    return c["tokens"] / c["elapsed_s"]
