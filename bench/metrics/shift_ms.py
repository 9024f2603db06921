"""Device milliseconds per step of the operations traced under the
``train/round/shift`` name scope, averaged over the cell's chips: the
shift rule's estimator and shift update (``rule.aux``, ``rule.apply``,
``resync_h_bar``)."""

import trace_scopes as TS

LAYER = "shift round and exchange"
UNIT = "ms"
MOVES = "tokens_per_s"
SCOPE = "train/round/shift"


def read(ctx):
    return TS.ms_per_step(ctx, SCOPE)
