"""End-to-end query observability (DESIGN.md §14).

Three instruments behind one disclosure audit boundary
(:mod:`repro.obs.redact`):

* :mod:`repro.obs.trace` — hierarchical lifecycle spans (query -> compile ->
  admit -> schedule.wait -> batch.flush -> execute -> node[op] -> sort /
  device.wait / xla.compile -> reveal -> record), thread-local like the
  :class:`~repro.core.ledger.CommLedger`, exported as structured JSONL and,
  while a tracer is active, written into any profiler trace as host events;
* :mod:`repro.obs.metrics` — a typed metrics registry (counters / gauges /
  histograms with audited label sets) rendered as Prometheus text exposition
  or a JSON snapshot;
* :mod:`repro.obs.explain` — EXPLAIN / EXPLAIN ANALYZE plan-tree rendering
  with estimated-vs-actual rows/seconds/bytes/rounds per node.

Telemetry about intermediate results is itself a disclosure channel
(Shrinkwrap's lesson): every emitted value passes ``redact.public_view`` —
only oblivious capacities and accountant-charged post-reveal sizes are
emittable; the true cardinality T and the noise draws p/eta never leave the
process through any span, metric, or EXPLAIN line.
"""
from . import redact
from .distributed import (
    TraceContext,
    WireMetricsPublisher,
    chrome_trace,
    clock_offset,
    merge_party_spans,
    write_chrome_trace,
)
from .explain import explain_text
from .metrics import Counter, Gauge, Histogram, MetricsRegistry
from .trace import Span, Tracer, active_tracer, record, set_attrs, span

__all__ = [
    "redact",
    "explain_text",
    "MetricsRegistry",
    "Counter",
    "Gauge",
    "Histogram",
    "Span",
    "Tracer",
    "active_tracer",
    "record",
    "set_attrs",
    "span",
    "TraceContext",
    "WireMetricsPublisher",
    "chrome_trace",
    "clock_offset",
    "merge_party_spans",
    "write_chrome_trace",
]
