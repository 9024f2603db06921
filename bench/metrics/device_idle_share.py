"""Share of the traced window in which the chip runs no operation,
averaged over the cell's chips: 1 - union(device op intervals) / window."""

import trace_reduce as TR

LAYER = "device"
UNIT = "%"
MOVES = "tokens_per_s"


def read(ctx):
    lo, hi = ctx.window
    busy = TR.mean_over_devices(ctx.trace, lambda ops: TR.busy_ns(ops, lo, hi))
    return 100.0 * (1.0 - busy / (hi - lo))
