"""Device milliseconds per step of the operations traced under the q8
codec's ``q8/uniform`` name scope, wherever the codec runs (messages,
ring hops; also wrapped as ``vmap(q8/uniform)``), averaged over the
cell's chips: the f32 uniforms of stochastic rounding.  A fusion is
credited to the scope of its root, so draws fused into a consumer
outside the scope are not counted here."""

import trace_scopes as TS

LAYER = "kernels"
UNIT = "ms"
MOVES = "tokens_per_s"
SCOPE = "q8/uniform"


def read(ctx):
    return TS.ms_per_step(ctx, SCOPE)
