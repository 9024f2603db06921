"""Host milliseconds per step spent making the batch and placing it on
the chips (``stream.batch`` + ``device_put``), from the benchmark's own
``bench/input`` spans in the trace."""

LAYER = "data"
UNIT = "ms"
MOVES = "tokens_per_s"


def read(ctx):
    lo, hi = ctx.window
    ns = sum(min(e, hi) - max(s, lo) for s, e, n in ctx.trace["host"]
             if n == "bench/input" and min(e, hi) > max(s, lo))
    return ns / 1e6 / ctx.steps
