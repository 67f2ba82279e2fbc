"""repro.obs — spans, metrics and fit reports for the whole stack.

Three layers, one import:

  * tracer  — thread-safe `span()` context managers on named lanes (driver +
    one lane per device producer), each with an id and its parent's, each
    mirrored as a `jax.profiler.TraceAnnotation` so a profile shows them
    beside the device ops; `Tracer.record()` for spans known after the fact; a gc
    hook and a stall watchdog while enabled. Near-free and allocation-free
    when disabled; export to Chrome trace-event JSON (Perfetto) or JSONL.
  * metrics — always-on counters/gauges/histograms in one registry
    (`engine.blocks_read`, `engine.bytes_h2d`, `engine.passes.<label>`,
    `serve.batch_size`, ...), scoped by snapshot/delta, thread-safe under the
    sharded executor's D producers.
  * report  — `FitReport`, the structured record every backend fit and sweep
    returns (phase wall-times, per-iteration inertia trajectory, pass counts,
    bytes, per-device block counts), plus the roofline join that compares
    measured phase time against `repro.roofline.analysis` terms.

See DESIGN.md §13 for the span taxonomy and metric-name table.
"""
from repro.obs.export import (
    chrome_trace_events,
    write_chrome_trace,
    write_jsonl,
    write_trace,
)
from repro.obs.metrics import (
    METRICS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    counter,
    delta,
    gauge,
    histogram,
    reset_metrics,
    scoped,
    snapshot,
)
from repro.obs.report import (
    FitReport,
    join_fit_roofline,
    report_from_metrics_delta,
    roofline_join,
)
from repro.obs.tracer import (
    NULL_SPAN,
    TRACER,
    Span,
    Tracer,
    clear_trace,
    disable_tracing,
    enable_tracing,
    set_lane,
    span,
    tracing_enabled,
)

__all__ = [
    "METRICS", "NULL_SPAN", "TRACER",
    "Counter", "FitReport", "Gauge", "Histogram", "MetricsRegistry", "Span",
    "Tracer",
    "chrome_trace_events", "clear_trace", "counter", "delta",
    "disable_tracing", "enable_tracing", "gauge", "histogram",
    "join_fit_roofline", "report_from_metrics_delta", "reset_metrics",
    "roofline_join", "scoped", "set_lane", "snapshot", "span",
    "tracing_enabled", "write_chrome_trace", "write_jsonl", "write_trace",
]
