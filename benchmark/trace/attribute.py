"""Idle gaps of a traced run put down to the program's own spans.

The program's spans come from an ``obs.Tracer`` bound for the traced
window, mapped onto the profiler's clock by
``jepsen_tpu.obs.profile.on_profile_clock`` through the window's
anchor. ``reduce_events(device, host, program)`` gives what
``profile.reduce_events`` gives, except that each idle gap is cut at the
edges of the program spans inside it and each piece is labelled by the
innermost program span that covers it; a piece no program span covers
keeps the benchmark's own label (``check``, ``handoff``, ``outside``).
With no program spans the result is ``profile.reduce_events``'s own.

Program spans are the engines' phase laps (``wgl.phase.*``) and every
span of cat ``dispatch`` or ``analysis`` (``independent.*``,
``analysis.certify``, ``certify.*``): ``program_spans`` picks them.
"""

from __future__ import annotations

import bisect
from dataclasses import dataclass, field

from benchmark.trace import profile

#: the tracer categories that name host work around the device
CATS = ("phase", "dispatch", "analysis")


@dataclass
class Attributed(profile.Reduced):
    #: span name -> seconds of the window the union of its intervals
    #: covers
    program_s: dict = field(default_factory=dict)
    #: seconds of the window inside any program span
    named_s: float = 0.0
    #: idle seconds whose piece carries a program-span label, averaged
    #: over the device planes like ``busy_s``
    idle_named_s: float = 0.0


def program_spans(mapped):
    """``(name, start_ns, dur_ns)`` of the mapped tracer events
    (``on_profile_clock``'s) that name host work."""
    return [(n, s, d) for n, cat, s, d in mapped or () if cat in CATS]


def _segments(prog):
    """Cut the time line at every program-span edge: the cut points, and
    for each piece between two neighbours the innermost (shortest)
    program span covering it, or None."""
    points = sorted({x for _, s, e in prog for x in (s, e)})
    starts = sorted(prog, key=lambda p: p[1])
    ends = sorted(prog, key=lambda p: p[2])
    active, labels = [], []
    i = j = 0
    for x in points[:-1]:
        while j < len(ends) and ends[j][2] <= x:
            active.remove(ends[j])
            j += 1
        while i < len(starts) and starts[i][1] <= x:
            if starts[i][2] > x:
                active.append(starts[i])
            i += 1
        labels.append(min(active, key=lambda p: p[2] - p[1])[0]
                      if active else None)
    return points, labels


def _pieces(a, b, points, labels):
    """Gap ``[a, b)`` cut at the program-span edges inside it:
    ``(start, end, label or None)``."""
    out = []
    k = bisect.bisect_right(points, a) - 1
    x = a
    while x < b:
        nxt = points[k + 1] if 0 <= k + 1 < len(points) else b
        y = min(nxt, b) if nxt > x else b
        label = labels[k] if 0 <= k < len(labels) else None
        out.append((x, y, label))
        x = y
        k += 1
    return out


def reduce_events(device, host, program=None):
    """See the module docstring. ``program``: ``(name, start_ns,
    dur_ns)`` on the profiler's clock, as ``program_spans`` gives."""
    base = profile.reduce_events(device, host)
    if base is None or not program:
        return base
    w0, w1 = [(s, s + d) for n, s, d in host if n == profile.WINDOW][0]
    marks = [(n, s, d) for n, s, d in host if n != profile.WINDOW]
    prog = [(n, s, s + d) for n, s, d in program
            if d > 0 and s < w1 and s + d > w0]
    points, labels = _segments(prog)
    gaps, named_idle = [], 0.0
    for plane in sorted(device):
        clipped = [(max(s, w0), min(s + d, w1))
                   for _, s, d in device[plane]
                   if min(s + d, w1) > max(s, w0)]
        _, merged = profile.union(clipped)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            for x, y, label in _pieces(a, b, points, labels):
                if label is None:
                    label = profile._label(marks, (x + y) / 2)
                else:
                    named_idle += y - x
                gaps.append((label, (y - x) / 1e9))
    program_s = {}
    for name in {p[0] for p in prog}:
        total, _ = profile.union([(max(s, w0), min(e, w1))
                                  for n, s, e in prog if n == name])
        program_s[name] = total / 1e9
    named, _ = profile.union([(max(s, w0), min(e, w1))
                              for _, s, e in prog])
    return Attributed(window_s=base.window_s, busy_s=base.busy_s,
                      device_ops=base.device_ops, gaps=gaps,
                      program_s=program_s, named_s=named / 1e9,
                      idle_named_s=named_idle / len(device) / 1e9)


def span_share(trace, name):
    """The window share of program span ``name``; None where the trace
    carries no program spans or no such span."""
    spans = getattr(trace, "program_s", None)
    if not spans or name not in spans or trace.window_s <= 0:
        return None
    return spans[name] / trace.window_s


def idle_attributed_share(trace):
    """Idle seconds carrying a program-span label over all idle seconds
    of the window; None where the trace carries no program spans."""
    if not getattr(trace, "program_s", None):
        return None
    idle = trace.window_s - trace.busy_s
    return trace.idle_named_s / idle if idle > 0 else None
