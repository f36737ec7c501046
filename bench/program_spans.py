"""What the program recorded about itself while the profiler ran.

The program's spans and counters (``repro.obs.trace``) go to one
process-wide recorder while a profiler session records, so after a
traced run it holds exactly the profiled part of the window.  The
readers of the ``program_span`` metrics divide a span total by the
counter the program records at the same boundary.  A checkout of the
program without that recorder, or a run that recorded nothing, reads as
nothing.
"""

from __future__ import annotations


def recorder():
    """The program's process recorder, or ``None`` where the program has
    none or it holds no span."""
    try:
        from repro.obs import process_recorder
    except ImportError:
        return None
    rec = process_recorder()
    return rec if len(rec.spans) else None


def spans(rec, cat: str, name: str) -> list:
    """The complete spans ``cat.name``."""
    return [e for e in rec.spans.events
            if e["ph"] == "X" and e["name"] == name and e["cat"] == cat]


def counter(rec, name: str, **labels) -> float:
    """Counter ``name`` with exactly ``labels`` (0 when never counted)."""
    for m in rec.metrics.snapshot():
        if m["name"] == name and m["labels"] == labels:
            return m["value"]
    return 0.0


def ms_per(cat: str, name: str, per: str, child: str | None = None,
           **labels) -> float | None:
    """Milliseconds of span ``cat.name`` per count of counter ``per``
    with ``labels``, less the time of each span's own ``child`` spans
    (same category) when ``child`` is given; ``None`` when the spans or
    the count are missing."""
    rec = recorder()
    if rec is None:
        return None
    found = spans(rec, cat, name)
    n = counter(rec, per, **labels)
    if not found or n <= 0:
        return None
    ids = {e["id"] for e in found}
    inner = sum(e["dur_us"] for e in spans(rec, cat, child)
                if e["parent"] in ids) if child else 0.0
    return 1e-3 * (sum(e["dur_us"] for e in found) - inner) / n


def mean_ms(cat: str, name: str) -> float | None:
    """Mean milliseconds of span ``cat.name``; ``None`` without one."""
    rec = recorder()
    found = spans(rec, cat, name) if rec is not None else []
    if not found:
        return None
    return 1e-3 * sum(e["dur_us"] for e in found) / len(found)
