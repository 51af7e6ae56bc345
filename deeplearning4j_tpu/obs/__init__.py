"""Unified observability: span tracing + one metrics registry.

Three small, dependency-free primitives every subsystem shares
(docs/OBSERVABILITY.md):

- :mod:`~.trace` — a low-overhead, thread-safe span recorder (bounded
  ring buffer, monotonic clocks) with a Chrome-trace-event JSON export
  (perfetto/chrome://tracing-loadable) and a merge tool that stitches
  the launcher's N per-worker trace files into one pod timeline.
- :mod:`~.startup` — load-time ``phase()`` spans that are counted
  whatever the sinks, and a watch on JAX's own compile events: where
  start-up goes, and that (and what) the process compiled.
- :mod:`~.metrics` — a typed MetricsRegistry (counters / gauges /
  fixed-bucket histograms, labeled) with one snapshot schema; the
  serving counters, elastic recovery counters, prefetch stall stats,
  and launcher membership stats all surface through it, so one
  ``/metrics`` response answers "what is this process doing".

The TensorFlow precedent (arxiv 1605.08695) ships step-span tracing and
a unified metrics surface as core infrastructure; the TPU-supercomputer
retrospective (arxiv 2606.15870) makes production debuggability the
gating concern at pod scale.  Tracing is OFF by default and the
disabled path is a few dict lookups — the ``telemetry_overhead`` bench
config hard-gates the enabled path at <= 3% step overhead and the
disabled path at bit-identical behavior.
"""

from .metrics import (
    Counter, Gauge, Histogram, MetricsRegistry, get_registry,
    merge_snapshots,
)
from .startup import (
    on_unphased_compile, phase, startup_account, watch_compiles,
)
from .trace import (
    TraceRecorder, disable_tracing, enable_tracing, get_recorder, instant,
    merge_traces, span, span_tree, tracing_enabled, traced,
    validate_chrome_trace,
)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "TraceRecorder",
    "disable_tracing", "enable_tracing", "get_recorder", "get_registry",
    "instant", "merge_snapshots", "merge_traces", "on_unphased_compile",
    "phase", "span", "span_tree", "startup_account", "traced",
    "tracing_enabled", "validate_chrome_trace", "watch_compiles",
]
