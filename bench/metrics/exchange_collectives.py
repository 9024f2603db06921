"""Collective operations (collective-permutes, all-reduces, ...) issued
per step under the ``train/round/exchange`` name scope on a chip,
averaged over the cell's chips: an asynchronous one counts once, by its
``-start``."""

import trace_reduce as TR
import trace_scopes as TS

LAYER = "shift round and exchange"
UNIT = "count"
MOVES = "tokens_per_s"
SCOPE = "train/round/exchange"


def read(ctx):
    lo, hi = ctx.window
    pat = TS.scope_re(SCOPE)
    if not TS.present(ctx.trace, pat, lo, hi):
        return None
    n = TR.mean_over_devices(
        ctx.trace, lambda ops: TS.issued_collectives(ops, pat, lo, hi))
    return n / ctx.steps
