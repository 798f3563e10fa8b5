"""Auxiliary subsystems (reference: src/auxiliary/ — Trace, Debug).

- aux.metrics: counters/gauges/timers/histograms registry,
  compile-vs-execute split, cost_analysis FLOP attribution, JSONL
  export (SLATE_TPU_METRICS=/path/out.jsonl).
- aux.spans: request-scoped span tracer — trace ids, parent/child
  spans, bounded ring-buffer flight recorder
  (SLATE_TPU_TRACE_RING=N), Chrome trace-event export for Perfetto,
  and profiler annotations that put driver phases and span blocks on
  the device trace's clock.
- aux.faults: deterministic seedable fault injection over named sites
  in the serve/driver dispatch path (SLATE_TPU_FAULTS spec).
- aux.devmon: device telemetry plane — per-executable cost/memory
  capture (cost_analysis + memory_analysis at build time), per-device
  memory gauges with graceful None on backends without memory_stats,
  and the roofline peaks table (SLATE_TPU_PEAKS override); armed by
  SLATE_TPU_DEVMON=1, one bool per call site when off.
- aux.sync: instrumented Lock/RLock/Condition runtime — Eraser-style
  lockset checking over `# guarded by:` fields, live lock-order cycle
  detection with both stacks of an inversion, happens-before hand-off
  edges (Condition wait/notify, Future resolution), and seeded
  replayable yield points; armed by SLATE_TPU_SYNC_CHECK=1, plain
  threading primitives (zero overhead) when off.
"""

from . import devmon, faults, metrics, spans, sync  # noqa: F401
