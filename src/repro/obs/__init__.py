"""repro.obs — unified telemetry: counters, event traces, spans, export.

One API for every layer of the stack:

- ``counters``: host ``CounterRegistry`` (codec fallbacks, per-kernel
  bytes-moved cost table) + jit-safe quant-health aggregates
  (clip/saturation fractions, scale drift) that bit-agree across codec
  backends.
- ``trace``: host-side ring-buffered ``TraceRecorder`` — engine/scheduler/
  train-driver structured events, zero device overhead; and ``span`` /
  ``step_span``, the engine's phases in the JAX profiler's trace.
- ``ledger``: byte-accurate live ``MemoryLedger`` — every allocation site
  (params, moments, residuals, KV/state pools, prefix pages) reports in;
  per-phase peak watermarks, ``jax.live_arrays()`` reconcile, live
  reduction-vs-fp32 figure.
- ``spans``: per-request span trees derived from the flat event log.
- ``export``: JSONL + Chrome-trace (Perfetto) writers.

See README "Observability" for the schema and interpretation guide.
"""
from .counters import (CounterRegistry, fraction, kernel_costs,
                       pow2_clip_stats, record_kernel_call, registry,
                       saturation_counts, scale_drift_stats, tree_sat_stats)
from .export import (chrome_trace, read_jsonl, write_chrome_trace,
                     write_jsonl)
from .ledger import PHASES, MemoryLedger, device_breakdown
from .spans import Span, check_nesting, request_spans
from .trace import Event, TraceRecorder, span, step_span

__all__ = [
    "CounterRegistry", "registry", "record_kernel_call", "kernel_costs",
    "pow2_clip_stats", "saturation_counts", "scale_drift_stats",
    "tree_sat_stats", "fraction",
    "Event", "TraceRecorder", "span", "step_span",
    "MemoryLedger", "device_breakdown", "PHASES",
    "Span", "request_spans", "check_nesting",
    "write_jsonl", "read_jsonl", "chrome_trace", "write_chrome_trace",
]
