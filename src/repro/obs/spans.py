"""Span tracing for host-side serving phases.

A :class:`SpanTracer` records *complete* spans (``ph == "X"``: name,
category, start, duration, args) and *instant* events (``ph == "i"``:
fault trips, quarantine edges) from the host half of the serving path —
planner, scan dispatch, admission, paging, checkpoint write/resume.  Two
export formats:

* ``write_jsonl(path)`` — one JSON object per line, the stable
  machine-readable schema validated by ``tests/test_obs.py``;
* ``write_chrome_trace(path)`` — the Chrome ``traceEvents`` JSON that
  ``chrome://tracing`` and Perfetto open directly.

Every span carries a process-unique ``id`` and the ``id`` of the span
open around it on the same thread (``parent``, 0 at the root), and
inherits its parent's request id (``args["rid"]``) unless it names its
own, so the spans of one request can be found and nested after the fact.

The tracer is a pure observer: it reads the clock around phases the
serving path already executes, keeps a bounded in-memory buffer
(overflow is *counted*, never silent), and touches no controller state.
"""

from __future__ import annotations

import itertools
import json
import threading
import time

# Default bound on buffered events; past it new events are dropped and
# counted in `dropped` (exported in both writers' metadata).
SPAN_BUFFER_CAP = 262144

# Required keys of one JSONL record, in write order.
JSONL_SCHEMA = ("name", "cat", "ph", "ts_us", "dur_us", "args", "id",
                "parent")
JSONL_VERSION = 2

_ids = itertools.count(1)
_open = threading.local()


def _stack() -> list:
    """This thread's open spans, innermost last."""
    stack = getattr(_open, "stack", None)
    if stack is None:
        stack = _open.stack = []
    return stack


def current_span():
    """The innermost open span on this thread, or ``None``."""
    stack = _stack()
    return stack[-1] if stack else None


class Span:
    """One open span, recorded into every tracer of ``tracers`` when it
    closes; ``note`` (a ``jax.profiler.TraceAnnotation`` or ``None``) is
    entered and left with it, so the span is also a host event in a
    running profiler's trace.  Times come from ``time.perf_counter``,
    every :class:`SpanTracer`'s default clock."""

    __slots__ = ("tracers", "name", "cat", "args", "note", "id",
                 "parent", "t0")

    def __init__(self, tracers, name: str, cat: str, args: dict,
                 note=None):
        self.tracers = tracers
        self.name = name
        self.cat = cat
        self.args = args
        self.note = note

    def __enter__(self) -> "Span":
        stack = _stack()
        up = stack[-1] if stack else None
        self.parent = up.id if up is not None else 0
        if up is not None and "rid" in up.args and "rid" not in self.args:
            self.args["rid"] = up.args["rid"]
        self.id = next(_ids)
        stack.append(self)
        if self.note is not None:
            self.note.__enter__()
            if self.args:
                self.note.set_metadata(**self.args)
        self.t0 = time.perf_counter()
        return self

    def set(self, **args) -> None:
        """Add ``args`` to the span while it is open (e.g. a value known
        only halfway through the block)."""
        self.args.update(args)
        if self.note is not None:
            self.note.set_metadata(**args)

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter()
        if self.note is not None:
            self.note.__exit__(*exc)
        stack = _stack()
        if stack and stack[-1] is self:
            stack.pop()
        for tr in self.tracers:
            tr.add(self.name, self.cat, self.t0, t1 - self.t0, self.args,
                   self.id, self.parent)
        return False


class SpanTracer:
    """Bounded in-memory recorder of phase spans and instant events."""

    def __init__(self, capacity: int = SPAN_BUFFER_CAP):
        self._t0 = time.perf_counter()
        self.capacity = int(capacity)
        self.events: list[dict] = []
        self.dropped = 0

    def _now_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    def _record(self, rec: dict) -> None:
        if len(self.events) < self.capacity:
            self.events.append(rec)
        else:
            self.dropped += 1

    def span(self, name: str, cat: str = "host", **args) -> Span:
        """Time the enclosed block as a complete span (``ph == "X"``)."""
        return Span((self,), name, cat, args)

    def add(self, name: str, cat: str, t0: float, dur_s: float,
            args: dict, span_id: int, parent: int) -> None:
        """Record a complete span that started at ``t0`` (perf-counter
        seconds) and lasted ``dur_s``."""
        self._record({"name": name, "cat": cat, "ph": "X",
                      "ts_us": (t0 - self._t0) * 1e6, "dur_us": dur_s * 1e6,
                      "args": dict(args), "id": span_id, "parent": parent})

    def event(self, name: str, cat: str = "host", **args) -> None:
        """Record an instant event (``ph == "i"``, zero duration), a
        child of the span open around it."""
        up = current_span()
        self._record({"name": name, "cat": cat, "ph": "i",
                      "ts_us": self._now_us(), "dur_us": 0.0,
                      "args": args, "id": next(_ids),
                      "parent": up.id if up is not None else 0})

    def __len__(self) -> int:
        return len(self.events)

    def phase_totals(self) -> dict[str, dict]:
        """Aggregate complete spans by name → count/total/max seconds."""
        out: dict[str, dict] = {}
        for e in self.events:
            if e["ph"] != "X":
                continue
            row = out.setdefault(e["name"],
                                 {"count": 0, "total_s": 0.0, "max_s": 0.0})
            dur_s = e["dur_us"] * 1e-6
            row["count"] += 1
            row["total_s"] += dur_s
            row["max_s"] = max(row["max_s"], dur_s)
        return out

    def write_jsonl(self, path: str) -> None:
        """Write one event per line; first line is a ``_meta`` header
        carrying the schema version and the dropped-event count."""
        with open(path, "w") as f:
            f.write(json.dumps({"_meta": {"schema": list(JSONL_SCHEMA),
                                          "version": JSONL_VERSION,
                                          "dropped": self.dropped}}))
            f.write("\n")
            for e in self.events:
                f.write(json.dumps({k: e[k] for k in JSONL_SCHEMA}))
                f.write("\n")

    def write_chrome_trace(self, path: str) -> None:
        """Write the Chrome/Perfetto ``traceEvents`` JSON."""
        events = []
        for e in self.events:
            rec = {"name": e["name"], "cat": e["cat"], "ph": e["ph"],
                   "ts": e["ts_us"], "pid": 0, "tid": 0,
                   "args": {**e["args"], "id": e["id"],
                            "parent": e["parent"]}}
            if e["ph"] == "X":
                rec["dur"] = e["dur_us"]
            else:
                rec["s"] = "t"  # instant scope: thread
            events.append(rec)
        doc = {"traceEvents": events,
               "displayTimeUnit": "ms",
               "otherData": {"dropped": self.dropped}}
        with open(path, "w") as f:
            json.dump(doc, f)
            f.write("\n")


def validate_jsonl(path: str) -> int:
    """Validate a :meth:`SpanTracer.write_jsonl` file against
    :data:`JSONL_SCHEMA`; returns the number of event records.

    Raises ``ValueError`` on a malformed header, missing keys, a bad
    ``ph`` code, or negative timestamps/durations — this is the schema
    check CI runs over every trace the tests emit.
    """
    n = 0
    with open(path) as f:
        header = json.loads(f.readline())
        meta = header.get("_meta")
        if meta is None or meta.get("schema") != list(JSONL_SCHEMA):
            raise ValueError(f"{path}: missing/mismatched _meta header")
        for lineno, line in enumerate(f, start=2):
            rec = json.loads(line)
            missing = [k for k in JSONL_SCHEMA if k not in rec]
            if missing:
                raise ValueError(f"{path}:{lineno}: missing {missing}")
            if rec["ph"] not in ("X", "i"):
                raise ValueError(f"{path}:{lineno}: bad ph {rec['ph']!r}")
            if rec["ts_us"] < 0 or rec["dur_us"] < 0:
                raise ValueError(f"{path}:{lineno}: negative time")
            if not isinstance(rec["args"], dict):
                raise ValueError(f"{path}:{lineno}: args not a dict")
            n += 1
    return n
