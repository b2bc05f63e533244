"""Reduction of the program's own host spans (``pbvd.*``) in a profiler trace.

The served path opens its spans with ``repro.launch.spans.span``; they lie
on the profiler's host lines, on the device planes' clock. Two reductions:

* self time: for each span name, the spans that start inside the window,
  and their time inside it less that of the ``pbvd.*`` spans nested in
  them on the same host line;
* idle attribution: of a device's idle time in the window, the part during
  which no ``pbvd.*`` span is open on any host line, and the rest by the
  innermost span open (the one that started last).

Events are ``(name, start, end)`` tuples in nanoseconds, one list per host
line; times come back in seconds.
"""

from __future__ import annotations

import collections

__all__ = [
    "PREFIX", "host_span_lines", "span_self_times", "idle_attribution", "per_launch_ms",
]

PREFIX = "pbvd."


def host_span_lines(planes) -> list[list[tuple]]:
    """The ``pbvd.*`` events of each host line of a ``ProfileData``'s planes."""
    lines = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [
                (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                for ev in line.events
                if ev.name.startswith(PREFIX)
            ]
            if evs:
                lines.append(evs)
    return lines


def span_self_times(lines, lo: float, hi: float) -> dict[str, list]:
    """``{name: [count, self seconds]}`` over the window ``[lo, hi)``."""
    out: dict[str, list] = {}
    for events in lines:
        stack = []  # (end, name) of the spans open at the current start
        for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
            while stack and stack[-1][0] <= s:
                stack.pop()
            entry = out.setdefault(name, [0, 0.0])
            if lo <= s < hi:
                entry[0] += 1
            t = max(0.0, min(e, hi) - max(s, lo)) * 1e-9
            entry[1] += t
            if stack and e <= stack[-1][0]:
                out[stack[-1][1]][1] -= t
            stack.append((e, name))
    return out


def idle_attribution(lines, idle, lo: float, hi: float) -> tuple[float, dict]:
    """(idle seconds with no span open, ``{name: idle seconds}`` by the
    innermost open span) of the sorted disjoint ``idle`` intervals."""
    marks = []
    for events in lines:
        for name, s, e in events:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                key = (s, -e, len(marks))
                marks.append((s, 1, key, name))
                marks.append((e, 0, key, name))
    marks.sort(key=lambda m: (m[0], m[1]))  # at one instant, ends before starts
    open_spans: dict[tuple, str] = {}
    by_span: collections.Counter = collections.Counter()
    unattributed = 0.0
    j, t = 0, lo

    def credit(a, b):
        nonlocal j, unattributed
        while j < len(idle) and idle[j][1] <= a:
            j += 1
        k, t_idle = j, 0.0
        while k < len(idle) and idle[k][0] < b:
            t_idle += max(0.0, min(b, idle[k][1]) - max(a, idle[k][0]))
            k += 1
        if t_idle:
            if open_spans:
                by_span[open_spans[max(open_spans)]] += t_idle * 1e-9
            else:
                unattributed += t_idle * 1e-9

    for at, starts, key, name in marks:
        if at > t:
            credit(t, at)
            t = at
        if starts:
            open_spans[key] = name
        else:
            del open_spans[key]
    if hi > t:
        credit(t, hi)
    return unattributed, dict(by_span)


def per_launch_ms(run, name: str):
    """Self time of span ``name`` in a run's window per ``pbvd.launch`` span
    started in it, in ms; None where the trace holds no launch span."""
    spans = (run.trace or {}).get("spans") or {}
    launches = spans.get(PREFIX + "launch", [0, 0.0])[0]
    if not launches:
        return None
    return spans.get(name, [0, 0.0])[1] / launches * 1e3
