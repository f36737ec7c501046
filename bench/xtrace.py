"""Reduce a profiler trace (``.xplane.pb``) to device busy time.

Device planes are those named ``/device:TPU:<n>``; on each, the events
of the ``XLA Ops`` line are the operations that ran.  Busy time is the
length of the union of their intervals inside the traced window, which
is the host annotation ``bench.window`` when the trace holds one (the
harness opens it around the traced part of the measured window) and
otherwise the span of all device events.  Idle gaps are the holes in
that union, each named by the innermost ``bench.*`` host annotation
running at its midpoint.
"""

from __future__ import annotations

import bisect
import collections
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench.window"
HOST_PREFIX = "bench."


def union(intervals):
    """Merged, sorted ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo, hi):
    """``intervals`` cut to ``[lo, hi]``, empty pieces dropped."""
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def _strip(name: str) -> str:
    """A module's name without its program id suffix."""
    return re.sub(r"\(\d+\)$", "", name)


def _op_name(name: str) -> str:
    """An op event's instruction name (the trace gives its HLO text)."""
    return name.split(" = ", 1)[0].lstrip("%")


def read_planes(profile):
    """``(devices, host)``: per device index its op events
    ``(start_ns, end_ns, name)``, and host ``bench.*`` annotations."""
    devices: dict = {}
    host = []
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(e.start_ns, e.end_ns, _op_name(e.name))
                           for e in line.events]
                elif line.name == MODULES_LINE:
                    mods = [(e.start_ns, e.end_ns, _strip(e.name))
                            for e in line.events]
            devices[int(m.group(1))] = (ops, sorted(mods))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.start_ns, e.end_ns, e.name)
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    return devices, host


def _module_of(mods, starts, t):
    """Name of the module event running at ``t`` (or ``""``); ``mods``
    sorted by start, ``starts`` their starts."""
    k = bisect.bisect_right(starts, t) - 1
    return mods[k][2] if k >= 0 and mods[k][1] >= t else ""


def reduce_profile(profile, top: int = 10) -> dict | None:
    """Busy and window seconds (busy averaged over devices), the ``top``
    operations by summed device time, and the ``top`` longest idle gaps
    with the host annotation running in each.  ``None`` when no device
    operation ran."""
    devices, host = read_planes(profile)
    devices = {k: v for k, v in devices.items() if v[0]}
    if not devices:
        return None
    win = [(s, e) for s, e, n in host if n == WINDOW]
    if win:
        lo, hi = min(s for s, _ in win), max(e for _, e in win)
    else:
        lo = min(s for ops, _ in devices.values() for s, _, _ in ops)
        hi = max(e for ops, _ in devices.values() for _, e, _ in ops)
    busy_ns = 0.0
    by_op: collections.Counter = collections.Counter()
    gaps = []
    spans = sorted((s, e, n) for s, e, n in host if n != WINDOW)
    for ops, mods in devices.values():
        starts = [m[0] for m in mods]
        merged = clip(union((s, e) for s, e, _ in ops), lo, hi)
        busy_ns += sum(e - s for s, e in merged)
        for s, e, name in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                mod = _module_of(mods, starts, (s + e) / 2)
                by_op[f"{mod}:{name}" if mod else name] += d
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps.extend((e - s, s, e) for s, e in zip(edges[0::2], edges[1::2])
                    if e > s)
    n_dev = len(devices)
    named = []
    for d, s, e in sorted(gaps, reverse=True)[:top]:
        mid = (s + e) / 2
        inner = [(ee - ss, n) for ss, ee, n in spans if ss <= mid <= ee]
        named.append((min(inner)[1] if inner else "host", d))
    return {
        "busy_s": busy_ns / n_dev * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "device_ops": [[n, d / n_dev * 1e-9]
                       for n, d in by_op.most_common(top)],
        "idle_gaps": [[n, d * 1e-9] for n, d in named],
    }


def reduce_file(path: str, top: int = 10) -> dict | None:
    """:func:`reduce_profile` of the ``.xplane.pb`` at ``path``."""
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path), top)


def idle_percent(trace: dict | None) -> float | None:
    """100 x (1 - busy / window) of a reduced trace; ``None`` without
    one."""
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
