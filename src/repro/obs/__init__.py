"""Observability: flight recorder for the whole serving path.

``repro.obs`` is the cross-cutting instrumentation layer (DESIGN.md
§11, docs/OBSERVABILITY.md): a :class:`~repro.obs.metrics.MetricsRegistry`
of counters/gauges/histograms/phase timers, a
:class:`~repro.obs.spans.SpanTracer` for host-side phases with JSONL and
Chrome-trace export, and a :class:`~repro.obs.ring.TelemetryRing` of
per-round aggregates fed straight from the megatick scan.  The three
are bundled by :class:`FlightRecorder`, the single object a gateway or
server accepts via its ``obs=`` keyword.  The serving path records
through :func:`~repro.obs.trace.span` and :func:`~repro.obs.trace.count`,
which also put every span into a running JAX profiler's trace and into
the process-wide :func:`~repro.obs.trace.process_recorder`.

Hard contract — **pure observer**: attaching a recorder leaves every
pick, bank state, and golden trace bitwise identical, and a disabled
recorder costs ~zero.  Both properties are asserted by
``tests/test_obs.py`` and ``benchmarks/controller_bench.py::bench_obs``.
"""

from __future__ import annotations

import os

from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               PhaseTimer)
from repro.obs.ring import RING_FIELDS, TelemetryRing
from repro.obs.spans import SpanTracer, validate_jsonl
from repro.obs.trace import (ProcessRecorder, count, process_recorder,
                             span)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "PhaseTimer",
    "TelemetryRing", "RING_FIELDS", "SpanTracer", "validate_jsonl",
    "FlightRecorder", "ProcessRecorder", "count", "process_recorder",
    "span",
]


class FlightRecorder:
    """The ``obs=`` bundle: metrics + spans + ring, with an off switch.

    ``FlightRecorder(enabled=False)`` is the asserted ~zero-cost mode:
    components check ``obs.enabled`` once at attach time and skip all
    instrumentation, so a disabled recorder behaves like ``obs=None``.
    """

    def __init__(self, enabled: bool = True, *,
                 ring_capacity: int | None = None):
        self.enabled = bool(enabled)
        self.metrics = MetricsRegistry()
        self.spans = SpanTracer()
        self.ring = TelemetryRing(ring_capacity) if ring_capacity \
            else TelemetryRing()

    def save(self, out_dir: str) -> dict[str, str]:
        """Write the whole recording under ``out_dir`` and return the
        paths: ``metrics.json``, ``spans.jsonl``, ``trace.json``
        (Chrome/Perfetto), ``ring.json``."""
        os.makedirs(out_dir, exist_ok=True)
        paths = {
            "metrics": os.path.join(out_dir, "metrics.json"),
            "spans": os.path.join(out_dir, "spans.jsonl"),
            "trace": os.path.join(out_dir, "trace.json"),
            "ring": os.path.join(out_dir, "ring.json"),
        }
        self.metrics.save(paths["metrics"])
        self.spans.write_jsonl(paths["spans"])
        self.spans.write_chrome_trace(paths["trace"])
        self.ring.save(paths["ring"])
        return paths
