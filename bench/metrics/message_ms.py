"""Device milliseconds per step of the operations traced under the
``train/round/message`` name scope, averaged over the cell's chips: the
workers' shift-rule messages Q(g - h), with the q8 codec's encode and
decode (core/shift_rules.py, kernels/q8ring/)."""

import trace_scopes as TS

LAYER = "shift round and exchange"
UNIT = "ms"
MOVES = "tokens_per_s"
SCOPE = "train/round/message"


def read(ctx):
    return TS.ms_per_step(ctx, SCOPE)
