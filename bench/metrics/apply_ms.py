"""Device milliseconds per step of the operations traced under the
``train/apply`` name scope, averaged over the cell's chips: the optimizer (optim/)."""

import trace_reduce as TR

LAYER = "optimizer"
UNIT = "ms"
MOVES = "tokens_per_s"
SCOPE = "train/apply"


def read(ctx):
    lo, hi = ctx.window
    ns = TR.mean_over_devices(ctx.trace,
                              lambda ops: TR.scope_ns(ops, SCOPE, lo, hi))
    return ns / 1e6 / ctx.steps if ns > 0 else None
