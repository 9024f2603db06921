"""Name-scope matching for the readers of the shift round's phases and
of the q8 codec (``metrics/message_ms.py`` and its siblings).

``trace_reduce.scope_ns`` finds a scope written as a plain run of path
segments (``train/round/message``).  A scope opened inside a JAX
transformation is written wrapped by it: inside the per-worker ``vmap``
of ``repro.comm.wire.on_workers`` the codec's ``q8/uniform`` reads
``.../vmap(q8/uniform)/jit(_uniform)/...``, and inside the ring's
``shard_map`` it follows a ``shard_map`` segment
(``train/round/exchange/bucket_0/shard_map/q8/uniform``).  ``scope_re``
matches the plain and the wrapped forms alike.
"""

from __future__ import annotations

import re

import trace_reduce as TR


def scope_re(scope: str):
    """``scope`` as whole path segments, bare or wrapped by any number
    of transformations (``vmap(...)``, ``jit(...)``)."""
    return re.compile(r"(?:^|/)(?:[\w.]+\()*" + re.escape(scope)
                      + r"\)*(?:/|$)")


def present(trace: dict, pattern, lo, hi) -> bool:
    """Whether any chip ran an operation under ``pattern`` in the window."""
    return any(e > lo and s < hi and pattern.search(sc)
               for ops in trace["devices"].values() for s, e, _, sc in ops)


def scope_ns(ops, pattern, lo, hi) -> int:
    """Device time of the operations whose scope matches ``pattern``:
    the length of the union of their intervals."""
    return TR.length(TR.clip(TR.union((s, e) for s, e, _, sc in ops
                                      if pattern.search(sc)), lo, hi))


def issued_collectives(ops, pattern, lo, hi) -> int:
    """Collective operations under ``pattern`` that start in the window,
    each counted once: the ``-start`` half of an asynchronous one (not
    its ``-done``) and every synchronous one."""
    return sum(1 for s, _, n, sc in ops
               if lo <= s < hi and TR.COLLECTIVE_RE.search(n)
               and "-done" not in n and pattern.search(sc))


def ms_per_step(ctx, scope: str):
    """Device ms per step under ``scope`` averaged over the cell's chips,
    or None when no chip ran anything under it."""
    lo, hi = ctx.window
    pat = scope_re(scope)
    if not present(ctx.trace, pat, lo, hi):
        return None
    ns = TR.mean_over_devices(ctx.trace,
                              lambda ops: scope_ns(ops, pat, lo, hi))
    return ns / 1e6 / ctx.steps
