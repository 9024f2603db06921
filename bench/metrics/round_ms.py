"""Device milliseconds per step of the operations traced under the
``train/round`` name scope, averaged over the cell's chips: the shift round and exchange (core/shift_rules.py, comm/, dist/collectives.py)."""

import trace_reduce as TR

LAYER = "shift round and exchange"
UNIT = "ms"
MOVES = "tokens_per_s"
SCOPE = "train/round"


def read(ctx):
    lo, hi = ctx.window
    ns = TR.mean_over_devices(ctx.trace,
                              lambda ops: TR.scope_ns(ops, SCOPE, lo, hi))
    return ns / 1e6 / ctx.steps if ns > 0 else None
