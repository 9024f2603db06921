"""Device milliseconds per step of the operations traced under the
``train/round/exchange`` name scope, averaged over the cell's chips: the
mean of the messages over the workers, on four chips the bucketed q8
ring (dist/collectives.py, comm/overlap.py)."""

import trace_scopes as TS

LAYER = "shift round and exchange"
UNIT = "ms"
MOVES = "tokens_per_s"
SCOPE = "train/round/exchange"


def read(ctx):
    return TS.ms_per_step(ctx, SCOPE)
