"""Reduce a profiler trace to device busy time, collectives and idle gaps.

The profiler writes an ``.xplane.pb``.  :func:`load_events` flattens it
into plain ``Event`` tuples (plane, line, name, start and end in seconds);
everything else works on those tuples, so the reduction can be tested on a
small recorded trace without a chip.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per HLO instruction run and their ``XLA Modules`` line one per
program run.  The benchmark's own host spans are ``TraceAnnotation``
events named ``bench.<what>`` on the host plane.  Device and host events
share the profiler's clock.
"""

from __future__ import annotations

import re
from collections import defaultdict
from typing import NamedTuple

__all__ = ["Event", "load_events", "device_planes", "union", "busy_in",
           "collective_s", "idle_gaps", "top_ops", "reduce_trace",
           "COLLECTIVE"]

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
# HLO opcodes of the collectives, synchronous or split into start/done.
COLLECTIVE = re.compile(
    r"\b(all-to-all|all-reduce|reduce-scatter|all-gather|"
    r"collective-permute|ragged-all-to-all)(-start|-done)?\b")


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start: float          # seconds on the profiler's clock
    end: float


def load_events(xplane_path: str) -> list[Event]:
    """Flatten an ``.xplane.pb`` into events (device ops and bench spans)."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(xplane_path).planes:
        dev = plane.name.startswith("/device:")
        for line in plane.lines:
            if dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if not dev and not ev.name.startswith(SPAN_PREFIX):
                    continue
                s = ev.start_ns * 1e-9
                out.append(Event(plane.name, line.name, ev.name, s,
                                 s + ev.duration_ns * 1e-9))
    return out


def device_planes(events: list[Event]) -> list[str]:
    return sorted({e.plane for e in events if e.line == OPS_LINE})


def union(intervals) -> list[tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy_in(events: list[Event], plane: str, lo: float, hi: float
            ) -> list[tuple[float, float]]:
    """Disjoint intervals in [lo, hi] in which an op ran on ``plane``."""
    return union(_clip([(e.start, e.end) for e in events
                        if e.plane == plane and e.line == OPS_LINE], lo, hi))


def op_kind(name: str) -> str | None:
    """The collective an HLO instruction is, or None."""
    m = COLLECTIVE.search(name)
    return m.group(1) if m else None


def collective_s(events: list[Event], plane: str, lo: float, hi: float
                 ) -> dict[str, float]:
    """Device seconds of each kind of collective on ``plane`` in the window.

    The time of one kind is the union of its ops' intervals, so an async
    start/done pair and the ops between them are not counted twice.
    """
    by_kind = defaultdict(list)
    for e in events:
        if e.plane == plane and e.line == OPS_LINE:
            kind = op_kind(e.name)
            if kind:
                by_kind[kind].append((e.start, e.end))
    return {k: sum(b - a for a, b in union(_clip(v, lo, hi)))
            for k, v in sorted(by_kind.items())}


def _spans(events: list[Event]) -> list[Event]:
    return [e for e in events if e.name.startswith(SPAN_PREFIX)
            and not e.plane.startswith("/device:")]


def _innermost(spans: list[Event]) -> tuple[list[float], list[str]]:
    """Cut the host timeline where any span starts or ends and name each
    piece by the innermost span open over it: (piece starts, names).

    Spans of one thread nest, so a stack sweep finds the innermost one.
    """
    marks = []
    for i, s in enumerate(spans):
        marks.append((s.start, 1, -(s.end - s.start), i))
        marks.append((s.end, 0, 0.0, i))
    marks.sort()
    stack: list[int] = []
    starts, names = [], []
    for t, is_start, _, i in marks:
        if is_start:
            stack.append(i)
        elif i in stack:
            stack.remove(i)
        name = spans[stack[-1]].name[len(SPAN_PREFIX):] if stack else "no span"
        if starts and starts[-1] == t:
            names[-1] = name
        else:
            starts.append(t)
            names.append(name)
    return starts, names


def idle_gaps(events: list[Event], plane: str, lo: float, hi: float,
              *, top: int = 10) -> list[list]:
    """The device's idle seconds in the window, summed by what the host
    was doing: each stretch of a gap is named by the innermost bench span
    open over it (``window`` where no other span is open).

    Returns ``[[span name, seconds], ...]``, the ``top`` largest sums.
    """
    import bisect

    busy = busy_in(events, plane, lo, hi)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    starts, names = _innermost(_spans(events))
    ends = starts[1:] + [float("inf")]
    tot: dict[str, float] = defaultdict(float)
    for a, b in zip(edges[0::2], edges[1::2]):
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        if not starts or a < starts[0]:
            tot["no span"] += min(b, starts[0] if starts else b) - a
        while i < len(starts) and starts[i] < b:
            x, y = max(a, starts[i]), min(b, ends[i])
            if y > x:
                tot[names[i]] += y - x
            i += 1
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])
            [:top]]


def _instruction(name: str) -> str:
    """``%fusion.3 = bf16[8,128]{...} fusion(...)`` -> ``fusion.3 bf16[8,128]``."""
    m = re.match(r"%?([\w.\-]+)\s*=\s*(\(?[a-z0-9]+\[[0-9,]*\])?", name)
    if not m:
        return name[:80]
    return f"{m.group(1)} {m.group(2) or ''}".strip()


def top_ops(events: list[Event], plane: str, lo: float, hi: float,
            *, top: int = 10) -> list[list]:
    """The ``top`` device ops by total seconds in the window, each named by
    its program and instruction: ``[[name, seconds], ...]``."""
    modules = sorted((e.start, e.end, e.name) for e in events
                     if e.plane == plane and e.line == MODULES_LINE)
    tot: dict[str, float] = defaultdict(float)
    j = 0
    for e in sorted((e for e in events
                     if e.plane == plane and e.line == OPS_LINE),
                    key=lambda e: e.start):
        if e.end <= lo or e.start >= hi:
            continue
        while j < len(modules) and modules[j][1] < e.start:
            j += 1
        mod = (modules[j][2].split("(")[0]
               if j < len(modules) and modules[j][0] <= e.start else "?")
        tot[f"{mod}:{_instruction(e.name)}"] += min(e.end, hi) - max(
            e.start, lo)
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])
            [:top]]


def reduce_trace(events: list[Event]) -> dict:
    """Per-device busy, collective and idle figures over the traced window.

    The window is the ``bench.window`` span; every figure is clipped to it.
    ``busy_s`` averages over the devices; ``busiest`` names the device
    with the most busy time, from which the breakdown is taken.
    """
    win = [e for e in _spans(events) if e.name == SPAN_PREFIX + "window"]
    if not win:
        raise ValueError("trace has no bench.window span")
    lo, hi = win[0].start, win[0].end
    per = {}
    for plane in device_planes(events):
        busy = sum(b - a for a, b in busy_in(events, plane, lo, hi))
        per[plane] = {"busy_s": busy,
                      "collective_s": collective_s(events, plane, lo, hi)}
    if not per:
        raise ValueError("trace has no device ops")
    busiest = max(per, key=lambda p: per[p]["busy_s"])
    return {
        "window_s": hi - lo,
        "busy_s": sum(p["busy_s"] for p in per.values()) / len(per),
        "devices": per,
        "busiest": busiest,
        "breakdown": {
            "device_ops": top_ops(events, busiest, lo, hi),
            "idle_gaps": idle_gaps(events, busiest, lo, hi),
        },
    }
