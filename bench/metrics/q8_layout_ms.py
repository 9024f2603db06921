"""Device milliseconds per step of the operations traced under the q8
codec's ``q8/layout`` name scope, wherever the codec runs (also wrapped
as ``vmap(q8/layout)``), averaged over the cell's chips: the f32
(rows, 128) lane layout in and out of the kernels and the decode's zero
accumulator."""

import trace_scopes as TS

LAYER = "kernels"
UNIT = "ms"
MOVES = "tokens_per_s"
SCOPE = "q8/layout"


def read(ctx):
    return TS.ms_per_step(ctx, SCOPE)
