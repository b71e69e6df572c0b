"""The program's own spans (``repro.serving.spans.SPANS``) for per-layer
readers, put on the trace's clock.

The engine keeps its spans while the run's capture runs, on
``time.monotonic()``; the trace keeps its events on a clock of its own.
The client wraps every ``eng.step()`` in a ``step`` span while that same
capture runs, and the engine opens one ``engine.step`` span inside
each, so the i-th of each kind start together: the program's spans move
onto the trace's clock by the median of the pairs' start differences.
Where the counts differ, or the recorder dropped spans, nothing is put
on the clock.

A program without the recorder (an older one), or one that holds no
span, reads as holding nothing: every function here then returns None.
"""
from __future__ import annotations

import bisect
import dataclasses
import statistics


def recorded():
    """The program's spans, or None where there is no recorder, it
    holds nothing, or it dropped spans."""
    try:
        from repro.serving.spans import SPANS
    except ImportError:
        return None
    if not SPANS.spans or SPANS.dropped:
        return None
    return SPANS.spans


def on_trace_clock(run):
    """The program's spans, moved onto the trace's clock (copies), or
    None where they cannot be."""
    spans = recorded()
    if spans is None or run.trace is None:
        return None
    mine = sorted((s for s in spans if s.name == "engine.step"),
                  key=lambda s: s.start)
    theirs = sorted((e for e in run.trace.host if e.name == "step"),
                    key=lambda e: e.start)
    if not mine or len(mine) != len(theirs):
        return None
    shift = statistics.median(t.start - s.start
                              for s, t in zip(mine, theirs))
    return [dataclasses.replace(s, start=s.start + shift,
                                end=s.end + shift) for s in spans]


def durations_in_window(run, name: str):
    """Durations (s) of the spans called ``name`` that start inside the
    window, or None where the spans cannot be put on the trace's
    clock."""
    spans = on_trace_clock(run)
    if spans is None:
        return None
    t0, t1 = run.trace.window
    return [s.dur for s in spans if s.name == name and t0 <= s.start < t1]


def _gaps(trace) -> list:
    """Idle intervals inside the window on the first plane, found as
    ``Trace.idle_gaps`` finds them."""
    t0, t1 = trace.window
    ops = sorted(trace._clip(trace.ops[trace.planes[0]]),
                 key=lambda e: e.start)
    gaps, cur = [], t0
    for e in ops:
        if e.start > cur:
            gaps.append((cur, e.start))
        cur = max(cur, e.end)
    if cur < t1:
        gaps.append((cur, t1))
    return gaps


def idle_by_span(run):
    """Device idle time inside the window, summed by the innermost
    ``engine.*`` span covering each gap's middle (``other`` where none
    does); None where the spans cannot be put on the trace's clock or
    the trace holds no device."""
    spans = on_trace_clock(run)
    if spans is None or not run.trace.planes:
        return None
    engine = sorted((s for s in spans if s.name.startswith("engine.")),
                    key=lambda s: (s.start, -s.end))
    starts = [s.start for s in engine]
    steps = [i for i, s in enumerate(engine) if s.name == "engine.step"]
    step_starts = [engine[i].start for i in steps]
    out: dict[str, float] = {}
    for s, t in _gaps(run.trace):
        mid = 0.5 * (s + t)
        label = "other"
        # Steps never overlap: only the last to start before the middle
        # can hold it, and its phases start inside it.
        k = bisect.bisect_right(step_starts, mid) - 1
        if k >= 0 and engine[steps[k]].end >= mid:
            lo = steps[k]
            hi = bisect.bisect_right(starts, mid)
            inner = [e for e in engine[lo:hi] if e.end >= mid]
            label = inner[-1].name
        out[label] = out.get(label, 0.0) + (t - s)
    return out
