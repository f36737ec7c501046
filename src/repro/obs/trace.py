"""Spans and counters on the profiler's clock, gated by the profiler.

:func:`span` and :func:`count` are the one way the serving path records
a phase or a tally:

* into the attached :class:`~repro.obs.FlightRecorder` when one is
  given and enabled, as ever;
* while a JAX profiler session records
  (``jax.profiler.TraceAnnotation.is_enabled()``), also as a host event
  ``<cat>.<name>`` in the profiler's own trace — on the device trace's
  clock, so any device idle gap can be put down to a program phase — and
  into the process-wide :func:`process_recorder`, which therefore holds
  exactly the spans and counts of the profiled window;
* otherwise not at all: with no recorder attached and no profiler
  running, a span or a count costs one ``is_enabled()`` check.

The process recorder has metrics and spans and no telemetry ring, and is
never a gateway's ``obs=``: the megatick's ring variant is a separate
compiled program, which would compile inside a profiled window.
"""

from __future__ import annotations

from jax.profiler import TraceAnnotation

from repro.obs.metrics import MetricsRegistry
from repro.obs.spans import Span, SpanTracer

_profiling = TraceAnnotation.is_enabled


class _NoSpan:
    """What :func:`span` gives when nothing records."""

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **args) -> None:
        """Nothing to add to."""


_NULL = _NoSpan()


class ProcessRecorder:
    """Metrics and spans recorded while the profiler runs."""

    def __init__(self):
        self.metrics = MetricsRegistry()
        self.spans = SpanTracer()

    def clear(self) -> None:
        """Forget everything recorded so far."""
        self.metrics = MetricsRegistry()
        self.spans = SpanTracer()

    def __len__(self) -> int:
        return len(self.spans) + len(self.metrics)


_PROCESS = ProcessRecorder()


def process_recorder() -> ProcessRecorder:
    """The process-wide recorder of the profiled windows."""
    return _PROCESS


def span(obs, name: str, cat: str, **args):
    """Context manager timing its block as span ``name`` of category
    ``cat`` with ``args`` (see the module docstring for where it goes).
    It yields the open :class:`~repro.obs.spans.Span`, whose
    ``set(**args)`` adds arguments known only inside the block (a no-op
    when nothing records)."""
    on = obs is not None and getattr(obs, "enabled", False)
    if _profiling():
        tracers = (obs.spans, _PROCESS.spans) if on else (_PROCESS.spans,)
        return Span(tracers, name, cat, args,
                    TraceAnnotation(f"{cat}.{name}"))
    if on:
        return Span((obs.spans,), name, cat, args)
    return _NULL


def count(obs, name: str, n: float = 1, **labels) -> None:
    """Add ``n`` to counter ``name`` with ``labels`` in the attached
    recorder and, while the profiler runs, in the process recorder."""
    if obs is not None and getattr(obs, "enabled", False):
        obs.metrics.counter(name, **labels).inc(n)
    if _profiling():
        _PROCESS.metrics.counter(name, **labels).inc(n)
