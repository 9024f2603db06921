"""Span API: profiler annotations inside jit + host wall-clock spans.

Two kinds of time live in a train step and they need different tools:

  * DEVICE time inside ``jit`` cannot be measured from Python (the host
    returns before the computation runs).  ``span(name)`` therefore
    wraps the region in ``jax.named_scope`` + ``jax.profiler.
    TraceAnnotation`` — both are TRACE-TIME context managers: they tag
    the emitted HLO / profiler timeline and add ZERO runtime ops, so
    annotating a phase can never change the math or force a recompile
    (pinned by the no-extra-compilation test in ``tests/test_obs.py``).
  * HOST time around jit boundaries (encode a delta, drain a reduction,
    apply a publish) is real wall clock.  When a ``SpanRecorder`` is
    active and we are NOT inside a trace, ``span`` also accumulates
    ``perf_counter`` durations into it.  With no recorder active the
    host path is a single ``is None`` check — the obs-off cost contract.

``StampRecorder`` is the raw begin/end-timestamp variant the overlap
channel uses: ``AsyncChannel.reduce_start``/``finish`` stamp their call
windows so ``repro.tune.measure.measure_overlap_hide`` can derive a
MEASURED hide fraction from the same handles the runtime schedules.
"""

from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Dict, List, Optional, Tuple

import jax

#: the active host-span recorder (None = host timing off; module-level
#: because spans are annotated at call sites that never see the driver)
_ACTIVE: Optional["SpanRecorder"] = None


def _host_clock_ok() -> bool:
    """True when a perf_counter span is meaningful — i.e. we are not
    inside a jax trace (where Python time measures TRACING, not the
    computation)."""
    return jax.core.trace_ctx.is_top_level()


class SpanRecorder:
    """Accumulated ``{name: (count, total_seconds)}`` host spans."""

    def __init__(self):
        self.spans: Dict[str, List[float]] = {}

    def add(self, name: str, seconds: float) -> None:
        cur = self.spans.setdefault(name, [0, 0.0])
        cur[0] += 1
        cur[1] += seconds

    def snapshot(self) -> dict:
        """{name: {count, total_s, mean_s}} — drops into a record."""
        return {
            name: {
                "count": int(c),
                "total_s": float(t),
                "mean_s": float(t) / c if c else None,
            }
            for name, (c, t) in self.spans.items()
        }

    def clear(self) -> None:
        self.spans.clear()


@contextmanager
def recording(recorder: SpanRecorder):
    """Activate ``recorder`` for host spans within the block."""
    global _ACTIVE
    prev, _ACTIVE = _ACTIVE, recorder
    try:
        yield recorder
    finally:
        _ACTIVE = prev


def active_recorder() -> Optional[SpanRecorder]:
    return _ACTIVE


@contextmanager
def span(name: str):
    """Annotate one phase (see module docstring).

    Safe anywhere: inside jit it is pure trace metadata; outside jit it
    additionally wall-clocks into the active ``SpanRecorder`` (if any).
    """
    rec = _ACTIVE
    timed = rec is not None and _host_clock_ok()
    t0 = time.perf_counter() if timed else 0.0
    with jax.profiler.TraceAnnotation(name), jax.named_scope(name):
        yield
    if timed:
        rec.add(name, time.perf_counter() - t0)


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
