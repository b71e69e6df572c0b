"""Reduction of a profiler trace (``.xplane.pb``) to device time.

The run wraps its measured window in a host span ``bench_window`` and
its own host work in spans (``wait_arrival``, ``submit``, ``step``,
``client``). From the trace this module takes:

- the device's operations (the ``XLA Ops`` line of each TPU device
  plane) and programs (``XLA Modules``), with start and end;
- the host spans, on the same clock.

Busy time is the union of the operation intervals inside the window;
idle gaps are the rest, each labelled by the host span that covers its
middle. A program's or kernel's time is the sum of its events, found by
a name that the program gives it (a jitted function's or a Pallas
kernel's), matched against the start of the event's own name.

    python -m bench.harness.trace <file.xplane.pb>   # what a trace holds
"""
from __future__ import annotations

import bisect
import re
import sys
from collections import Counter, defaultdict
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_SPANS = ("wait_arrival", "submit", "step", "client")
WINDOW_SPAN = "bench_window"
_NUM = re.compile(r"\.\d+$")


@dataclass
class Event:
    name: str
    start: float          # seconds, on the trace's clock
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start

    @property
    def short(self) -> str:
        """The operation's or program's own name, without its instance
        number or hash: ``%paged_attention.3 = ...`` -> ``%paged_attention``,
        ``jit__fused_paged_fn(123)`` -> ``jit__fused_paged_fn``."""
        head = self.name.split(" ", 1)[0].split("(", 1)[0]
        return _NUM.sub("", head)


@dataclass
class Trace:
    """What one trace holds, per device plane (ops and modules) and on
    the host (the benchmark's spans)."""
    ops: dict = field(default_factory=dict)        # plane -> [Event]
    modules: dict = field(default_factory=dict)    # plane -> [Event]
    host: list = field(default_factory=list)       # [Event]
    window: tuple = (0.0, 0.0)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def planes(self) -> list:
        return sorted(self.ops)

    def _clip(self, events):
        t0, t1 = self.window
        return [e for e in events if e.end > t0 and e.start < t1]

    def busy_s(self, plane: str) -> float:
        """Union of the plane's operation intervals inside the window."""
        t0, t1 = self.window
        total, cur_s, cur_e = 0.0, None, None
        for e in sorted(self._clip(self.ops[plane]), key=lambda e: e.start):
            s, t = max(e.start, t0), min(e.end, t1)
            if cur_e is None or s > cur_e:
                if cur_e is not None:
                    total += cur_e - cur_s
                cur_s, cur_e = s, t
            else:
                cur_e = max(cur_e, t)
        if cur_e is not None:
            total += cur_e - cur_s
        return total

    def mean_busy_s(self) -> float:
        return sum(self.busy_s(p) for p in self.planes) / max(
            1, len(self.planes))

    def seconds(self, pattern: str, kind: str = "ops") -> float:
        """Summed time of the window's events whose own name (``short``)
        matches ``pattern`` (a regular expression, anchored at the
        start), averaged over planes."""
        rx = re.compile(pattern)
        src = self.ops if kind == "ops" else self.modules
        tot = sum(e.dur for p in src for e in self._clip(src[p])
                  if rx.match(e.short))
        return tot / max(1, len(src))

    def top_ops(self, n: int = 10) -> list:
        """The device operations that took most time, by name with its
        instance number dropped, averaged over planes."""
        agg = Counter()
        for p in self.planes:
            for e in self._clip(self.ops[p]):
                agg[e.short] += e.dur
        k = max(1, len(self.planes))
        return [[name, s / k] for name, s in agg.most_common(n)]

    def idle_gaps(self, n: int = 10) -> list:
        """Idle device time inside the window, summed by the host span
        that covers each gap's middle (``other`` where none does);
        first plane. Largest first."""
        if not self.planes:
            return []
        t0, t1 = self.window
        ops = sorted(self._clip(self.ops[self.planes[0]]),
                     key=lambda e: e.start)
        gaps, cur = [], t0
        for e in ops:
            if e.start > cur:
                gaps.append((cur, e.start))
            cur = max(cur, e.end)
        if cur < t1:
            gaps.append((cur, t1))
        spans = sorted((e for e in self.host if e.name in HOST_SPANS),
                       key=lambda e: e.start)
        starts = [e.start for e in spans]
        agg = defaultdict(float)
        for s, t in gaps:
            # The client's spans run one after another, never nested,
            # so only the last one to start before the middle can hold it.
            mid = 0.5 * (s + t)
            i = bisect.bisect_right(starts, mid) - 1
            label = (spans[i].name if i >= 0 and spans[i].end >= mid
                     else "other")
            agg[label] += t - s
        return sorted(([k, v] for k, v in agg.items()),
                      key=lambda kv: -kv[1])[:n]


def load(path: str) -> Trace:
    """Read one ``.xplane.pb`` into a ``Trace``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    tr = Trace()
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name not in ("XLA Ops", "XLA Modules"):
                    continue
                evs = [Event(e.name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9)
                       for e in line.events]
                dst = tr.ops if line.name == "XLA Ops" else tr.modules
                dst.setdefault(plane.name, []).extend(evs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS or e.name == WINDOW_SPAN:
                        tr.host.append(Event(
                            e.name, e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9))
    win = [e for e in tr.host if e.name == WINDOW_SPAN]
    if win:
        tr.window = (win[0].start, win[0].end)
    for d in (tr.ops, tr.modules):
        for p in d:
            d[p].sort(key=lambda e: e.start)
    return tr


def describe(path: str, n: int = 25) -> str:
    """Planes, lines, event counts and the commonest names: what to
    look at before writing a reduction against a new trace."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            names = Counter(Event(e.name, 0, 0).short for e in evs)
            out.append(f"  LINE {line.name!r}: {len(evs)} events; "
                       f"first at {evs[0].start_ns if evs else None} ns")
            for name, c in names.most_common(n if "XLA" in line.name
                                             else 5):
                ex = next(e for e in evs if Event(e.name, 0, 0).short == name)
                out.append(f"    {c:7d} x {name!r} | {ex.name[:200]!r}")
    return "\n".join(out)


if __name__ == "__main__":
    print(describe(sys.argv[1]))
