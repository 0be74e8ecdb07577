"""repro.obs — unified telemetry: stats registry, trace spans, layer scopes,
run reports.

Four stdlib-only modules (no jax/numpy: importable from every layer without
cost, including spawned worker processes before jax initializes):

* ``metrics`` — the one ``rate`` definition, :func:`merge_stats` (THE way
  multiple stat dicts fold into one: sums counters, preserves non-numeric
  keys, recomputes every ``*_rate`` from the summed counters — never by
  averaging rates), and the process-local :class:`MetricsRegistry` that the
  ``*Stats`` dataclasses register themselves into.
* ``trace`` — span recording with two sinks. Off by default:
  ``trace.span(...)`` returns a shared no-op when every sink is off
  (nanoseconds per call), so instrumentation stays in the hot paths
  permanently. The JSONL sink (``trace.start``) writes Chrome-trace events;
  multi-process runs follow the store's segment pattern
  (``trace.jsonl.worker-<k>``) and :func:`trace.merge` produces one
  Perfetto/chrome://tracing-viewable file with per-worker tracks. The
  profiler sink (``trace.annotate(True)``, which imports jax) makes each
  span a ``jax.profiler.TraceAnnotation``, on the device profile's clock.
* ``scopes`` — the names of the ``jax.named_scope`` layer scopes the step
  programs open (``embed``, ``attention``, ``mlp``, ``mixer``, ``ssd``,
  ``lm_head``, ``loss``, ``optimizer``); names only, the reader of a
  profile decides which op falls in which.
* ``report`` — merges a run's trace + metrics into a human-readable report
  (``scripts/obs_report.py``).

Tracing is purely observational: it never touches RNG streams, store keys,
record bytes or checkpoint payloads, so traced runs are bitwise-identical
to untraced ones; scopes change op metadata only.
"""

from repro.obs import metrics, scopes, trace  # noqa: F401
from repro.obs.metrics import REGISTRY, MetricsRegistry, merge_stats, rate  # noqa: F401
from repro.obs.trace import span  # noqa: F401
