"""Device milliseconds per step in which a collective operation (the
ring's collective-permutes, all-reduces, ...) runs on a chip and no other
operation does, averaged over the cell's chips."""

import trace_reduce as TR

LAYER = "shift round and exchange"
UNIT = "ms"
MOVES = "tokens_per_s"


def read(ctx):
    if ctx.chips < 2:
        return None
    lo, hi = ctx.window
    ns = TR.mean_over_devices(ctx.trace,
                              lambda ops: TR.exposed_collective_ns(ops, lo, hi))
    return ns / 1e6 / ctx.steps
