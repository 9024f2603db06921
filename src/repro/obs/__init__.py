"""repro.obs — unified observability: per-wire telemetry, step tracing,
and measured-vs-predicted accounting.

One record schema (``metrics``), one span API (``trace``), one sink
discipline (``sink``), one export surface (``export``):

  ``metrics``  typed counters/gauges/histograms + the versioned
               strict-JSON ``StepRecord`` schema and THE repo-wide
               ``finite_or_none``/``sanitize_tree`` helpers.
  ``trace``    ``span(name)`` — ``jax.named_scope``/``TraceAnnotation``
               inside jit (zero runtime ops, no recompiles), read
               from a profiler trace of the compiled step;
               ``StampRecorder`` for the overlap channel's
               reduce_start/finish call windows.
  ``sink``     rotating strict-JSONL, memory, tee, null sinks; every
               record is sanitized + schema-validated before it is
               serialized.
  ``export``   end-of-run summary table, Prometheus text exposition,
               and the CI ``--check`` schema gate.
  ``quality``  measured distortion: ``omega_hat``/NMSE through the
               codecs' real encode paths (jit-compatible diagnostics).
  ``history``  the bench trajectory ledger: BENCH_*.json flattened into
               ``history.jsonl`` keyed by git sha x config fingerprint.
  ``regress``  the CI regression gate over that ledger's baselines
               (per-metric-class tolerance bands, non-zero exit).

THE CONTRACT (tested): with observability off, the trainer step is
bit-exact with the uninstrumented step and the jit path pays nothing —
spans are trace metadata, sinks are never constructed, and diagnostics
are not computed.
"""

from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    Metrics,
    RECORD_KINDS,
    SCHEMA_VERSION,
    event_record,
    finite_or_none,
    make_record,
    run_record,
    sanitize_tree,
    step_record,
    summary_record,
    validate_record,
)
from repro.obs.sink import (
    JsonlSink,
    MemorySink,
    NullSink,
    TeeSink,
    check_jsonl,
    read_jsonl,
    write_strict_json,
)
from repro.obs.trace import (
    StampRecorder,
    span,
)
from repro.obs.export import (
    format_table,
    prometheus_text,
    summarize,
    summary_table,
)
from repro.obs.quality import (
    array_distortion,
    distortion_floats,
    tree_distortion,
)

# NOTE: ``history`` and ``regress`` are CLI-first submodules (`python -m
# repro.obs.history` / ``.regress``) — import them explicitly; an eager
# import here would trip runpy's double-import warning under ``-m``.

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "JsonlSink",
    "MemorySink",
    "Metrics",
    "NullSink",
    "RECORD_KINDS",
    "SCHEMA_VERSION",
    "StampRecorder",
    "TeeSink",
    "array_distortion",
    "check_jsonl",
    "distortion_floats",
    "event_record",
    "finite_or_none",
    "format_table",
    "make_record",
    "tree_distortion",
    "prometheus_text",
    "read_jsonl",
    "run_record",
    "sanitize_tree",
    "span",
    "step_record",
    "summarize",
    "summary_record",
    "summary_table",
    "validate_record",
    "write_strict_json",
]
