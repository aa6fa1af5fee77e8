"""Observability subsystem: query-lifecycle spans, process-local metrics,
and exporters (JSONL spans, Chrome-trace JSON, Prometheus text).

* ``trace``   — one call site, ``trace.span(...)``, two sinks.  While a
  ``jax.profiler`` trace is being collected, each span is a host
  ``TraceAnnotation`` named ``fatrq.<name>`` on the device trace's clock;
  the device work of each layer carries a ``jax.named_scope`` of its own
  (``anns/stages.py``), so a profile shows which layer a device op or an
  idle gap belongs to.  With a ``Tracer`` active, spans are also kept in
  memory with wall and virtual-clock timestamps and free-form
  attributes.  No span waits for the device.  With neither sink on,
  ``span`` is a context-var read and a profiler check returning a shared
  no-op handle; no jit-visible work either way (pinned in
  ``tests/test_obs.py``).
* ``metrics`` — process-local registry of counters / gauges /
  histograms with label sets; the serving engine keeps one per engine,
  everything else uses the active (default) registry.
* ``export``  — JSONL span dump (byte-deterministic under the virtual
  clock), Chrome-trace/Perfetto JSON rendered from virtual-clock spans,
  and Prometheus text exposition of a registry.
"""

from repro.obs import export, metrics, trace
from repro.obs.metrics import Counter, Gauge, Histogram, MetricsRegistry
from repro.obs.trace import NOOP_SPAN, Span, Tracer

__all__ = ["export", "metrics", "trace",
           "Counter", "Gauge", "Histogram", "MetricsRegistry",
           "NOOP_SPAN", "Span", "Tracer"]
