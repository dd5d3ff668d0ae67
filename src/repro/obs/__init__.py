"""Engine telemetry: spans, metrics, layer names, model-vs-measured drift.

The observability layer the rest of the stack reports into (DESIGN.md
§15). Its parts are stdlib-only at import, so any core module may
import them without cycles:

* :mod:`repro.obs.trace` — spans as ``jax.profiler.TraceAnnotation``
  host events, on the profiler's clock beside the device ops (context
  manager + decorator). Disabled by default; enabled via
  ``$REPRO_TRACE`` or :func:`tracing`, which also records a profiler
  trace into a directory. When disabled a span call returns one shared
  no-op object — no allocation, no clock read.
* :mod:`repro.obs.scopes` — the names the program gives its layers on
  the device trace (kernel names, ``jax.named_scope`` layers) and the
  map from a compiled program's instructions to them.
* :mod:`repro.obs.metrics` — a process-wide registry of counters,
  gauges and histograms with ``snapshot()``/``reset()`` and JSON
  export. Always live (a counter bump is a dict add); the registry
  allocates state only for metrics actually touched.
* :mod:`repro.obs.drift` — pairs each launch's predicted §5
  ``model_cost`` cycles with measured µs and ranks the
  (signature, backend, strategy) cells whose calibration drifts from
  the backend-wide ratio — the artifact perf-model recalibration
  consumes (``python -m repro.obs.report``).

Overhead policy: with tracing off and per-call drift sampling off, the
hot path pays one module-level boolean check per instrumentation point
(asserted by ``tests/test_obs.py``). Telemetry never changes results —
every hook is read-only on the data path, and a named scope is
compile-time metadata.
"""
from __future__ import annotations

from . import drift, metrics, scopes, trace
from .trace import span, tracing

__all__ = ["drift", "metrics", "scopes", "trace", "span", "tracing"]
