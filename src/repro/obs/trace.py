"""Span API: profiler annotations inside jit + host call-window stamps.

DEVICE time inside ``jit`` cannot be measured from Python (the host
returns before the computation runs).  ``span(name)`` therefore wraps a
region in ``jax.named_scope`` + ``jax.profiler.TraceAnnotation`` — both
are TRACE-TIME context managers: they tag the emitted HLO (every
instruction's ``op_name`` metadata carries the scope path) and the
profiler timeline, and add ZERO runtime ops, so annotating a phase can
never change the math or force a recompile (pinned by the
no-extra-compilation test in ``tests/test_obs.py``).  A profiler trace
of the compiled step then credits each device operation to its scope
path, which is how the benchmark's per-layer readers
(``bench/metrics/``) split the step.

The train step's scopes: ``train/grads``, ``train/round`` (children
``message``, ``exchange`` and ``shift``, placed by the channels in
``repro.comm``), ``train/apply``; the q8 codec's ``q8/uniform`` and
``q8/layout`` nest wherever the codec runs.

``StampRecorder`` is the raw begin/end-timestamp recorder the overlap
channel uses: ``AsyncChannel.reduce_start``/``finish`` stamp their call
windows so ``repro.tune.measure.measure_overlap_hide`` can derive a
MEASURED hide fraction from the same handles the runtime schedules.
"""

from __future__ import annotations

import time
from contextlib import contextmanager
from typing import List, Tuple

import jax


def _host_clock_ok() -> bool:
    """True when a perf_counter stamp is meaningful — i.e. we are not
    inside a jax trace (where Python time measures TRACING, not the
    computation)."""
    return jax.core.trace_ctx.is_top_level()


@contextmanager
def span(name: str):
    """Annotate one phase (see module docstring): pure trace metadata
    inside jit, a profiler annotation outside it."""
    with jax.profiler.TraceAnnotation(name), jax.named_scope(name):
        yield


class StampRecorder:
    """Raw ``(name, t_begin, t_end)`` call-window stamps.

    The overlap channel's ``reduce_start``/``finish`` stamp here (host
    side only — stamping is skipped during tracing, so attaching a
    recorder never perturbs a jitted pipeline).
    """

    def __init__(self):
        self.events: List[Tuple[str, float, float]] = []

    @contextmanager
    def stamp(self, name: str):
        if not _host_clock_ok():
            yield
            return
        t0 = time.perf_counter()
        yield
        self.events.append((name, t0, time.perf_counter()))

    def clear(self) -> None:
        self.events.clear()

    def windows(self, name: str) -> List[Tuple[float, float]]:
        return [(t0, t1) for n, t0, t1 in self.events if n == name]

    def total(self, name: str) -> float:
        return sum(t1 - t0 for t0, t1 in self.windows(name))
