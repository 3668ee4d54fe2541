"""repro.obs — phase-level tracing, counters, and wall-clock telemetry.

Public surface:

  span(name, **args)    nestable timing context manager (no-op when off)
  count(name, n=1)      named counter (no-op when off)
  enable() / disable()  install / remove the global tracer (default: off);
                        enable(sync=False) observes without ever waiting
                        on the device
  enabled()             is a tracer installed?
  syncing()             ... and may instrumented code block to time a span?
  tracing()             scoped enable (tests)
  metrics_summary()     counters + per-phase aggregates + hit rates
  write_chrome_trace()  Perfetto/chrome://tracing-compatible trace.json
  write_jsonl()         flat one-object-per-line event log
  log_record()          structured launcher progress (REPRO_LOG=1 toggle)

While a tracer is installed every span is also a
`jax.profiler.TraceAnnotation`, so a profiler trace shows the program's
spans on the device ops' clock.

Imports nothing heavy (no jax/numpy; jax only once a tracer is made):
safe to wire into every layer.
"""
from repro.obs.export import chrome_trace, write_chrome_trace, write_jsonl
from repro.obs.logging import log_enabled, log_record, set_logging
from repro.obs.trace import (
    Tracer,
    count,
    disable,
    enable,
    enabled,
    get_tracer,
    metrics_summary,
    span,
    syncing,
    tracing,
)

__all__ = [
    "Tracer",
    "chrome_trace",
    "count",
    "disable",
    "enable",
    "enabled",
    "get_tracer",
    "log_enabled",
    "log_record",
    "metrics_summary",
    "set_logging",
    "span",
    "syncing",
    "tracing",
    "write_chrome_trace",
    "write_jsonl",
]
