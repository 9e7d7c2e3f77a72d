"""Observability: span tracing, metrics, export, per-term attribution.

The layer that turns the cost model's predictions into falsifiable
per-term measurements (docs/OBSERVABILITY.md). Import surface:

    from repro.obs import Recorder, current_recorder, use_recorder
    from repro.obs import LAYER_SCOPES, compile_counts
    from repro.obs import Metrics, StragglerMonitor
    from repro.obs import write_jsonl, read_jsonl
    from repro.obs import attribution_table, detect_drift
"""
from repro.obs.attribution import (DriftReport, TermRow, attribution_table,
                                   detect_drift, measure_collective_terms,
                                   predicted_step_ms, predicted_terms,
                                   render_markdown, span_coverage)
from repro.obs.export import TraceData, read_jsonl, trace_lines, write_jsonl
from repro.obs.metrics import (CompileCounts, Counter, Gauge, Histogram,
                               Metrics, StragglerMonitor, collective_bytes,
                               compile_counts,
                               device_memory_watermarks, observe_step,
                               record_collective_bytes,
                               record_memory_watermarks, record_recovery,
                               straggler_skew)
from repro.obs.trace import (LAYER_SCOPES, NULL_SPAN, Recorder, Span,
                             current_recorder, set_recorder, use_recorder)

__all__ = [
    "Recorder", "Span", "NULL_SPAN", "current_recorder", "set_recorder",
    "use_recorder", "LAYER_SCOPES",
    "Metrics", "Counter", "Gauge", "Histogram", "StragglerMonitor",
    "observe_step", "collective_bytes", "record_collective_bytes",
    "device_memory_watermarks", "record_memory_watermarks",
    "record_recovery", "straggler_skew", "CompileCounts", "compile_counts",
    "TraceData", "trace_lines", "write_jsonl", "read_jsonl",
    "TermRow", "DriftReport", "predicted_terms", "predicted_step_ms",
    "measure_collective_terms", "attribution_table", "render_markdown",
    "span_coverage", "detect_drift",
]
