"""Device milliseconds per step of the operations traced under the
``train/grads`` name scope, averaged over the cell's chips: the model forward and backward (models/, dist/worker_grads.py)."""

import trace_reduce as TR

LAYER = "model"
UNIT = "ms"
MOVES = "tokens_per_s"
SCOPE = "train/grads"


def read(ctx):
    lo, hi = ctx.window
    ns = TR.mean_over_devices(ctx.trace,
                              lambda ops: TR.scope_ns(ops, SCOPE, lo, hi))
    return ns / 1e6 / ctx.steps if ns > 0 else None
