"""Engine spans: the serving engine's own boundaries, kept while a
profiler capture runs.

``SPANS`` records if and only if a JAX profiler capture is running
(``jax.profiler.TraceAnnotation.is_enabled()``, true between
``jax.profiler.start_trace`` and ``stop_trace``); it has no switch of
its own. With no capture a site costs that one check and gets the
shared ``NULL_SPAN`` back. While a capture runs, each span is kept in
memory (name, start and end on ``time.monotonic()`` — the engine's own
clock base, so an engine time converts by adding ``engine.t0`` — its
id, its parent's id, the request it belongs to, attributes). A span
that nests inside one call (``span()``) is also opened as a
``TraceAnnotation`` of the same name and attributes, so the capture
shows it on the host plane, on the device planes' clock. Spans whose
times were already held when they are recorded (``record()``: adapter
loads, a request's phases) are kept but not mirrored.

The buffer keeps the first ``CAPACITY`` spans and counts the rest in
``dropped``; ``clear()`` (called by ``ChameleonEngine.reset_stats``)
empties it. ``docs/OPERATIONS.md`` lists the names the engine records
and what each covers.

jax is imported on first use, so importing this module costs nothing.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Optional

CAPACITY = 1 << 17


@dataclass
class Span:
    name: str
    start: float                  # time.monotonic() seconds
    end: float
    id: int
    parent: Optional[int] = None
    req_id: Optional[int] = None
    attrs: dict = field(default_factory=dict)

    @property
    def dur(self) -> float:
        return self.end - self.start


class _NullSpan:
    """What a site gets while no capture runs: keeps nothing."""
    id = None

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def __bool__(self) -> bool:
        return False

    def set(self, **attrs) -> None:
        pass


NULL_SPAN = _NullSpan()


def _mirrored(attrs: dict) -> dict:
    """Attributes as a TraceAnnotation takes them: numbers and strings
    as they are, anything else (a list of request ids) as its text."""
    return {k: v if isinstance(v, (int, float, str)) else str(v)
            for k, v in attrs.items()}


class _OpenSpan:
    """One span of ``SpanRecorder.span`` while its block runs."""
    __slots__ = ("_rec", "span", "_ann")

    def __init__(self, rec: "SpanRecorder", span: Span):
        self._rec = rec
        self.span = span
        self._ann = None

    @property
    def id(self) -> int:
        return self.span.id

    def __bool__(self) -> bool:
        return True

    def __enter__(self):
        from jax.profiler import TraceAnnotation
        rec, sp = self._rec, self.span
        sp.parent = rec.current()
        rec._stack.append(sp.id)
        self._ann = TraceAnnotation(sp.name, **_mirrored(sp.attrs))
        self._ann.__enter__()
        sp.start = time.monotonic()
        return self

    def __exit__(self, *exc) -> bool:
        # Stamped just inside the annotation's start and just outside
        # its end, so the two agree to the cost of one clock read.
        self._ann.__exit__(*exc)
        self.span.end = time.monotonic()
        self._rec._stack.pop()
        self._rec._keep(self.span)
        return False

    def set(self, **attrs) -> None:
        """Add attributes known only inside the block."""
        self.span.attrs.update(attrs)
        self._ann.set_metadata(**_mirrored(attrs))


class SpanRecorder:
    """A bounded in-memory buffer of spans, on while ``switch()`` is
    true (by default: while a JAX profiler capture runs)."""

    def __init__(self, capacity: int = CAPACITY,
                 switch: Optional[Callable[[], bool]] = None):
        self.capacity = capacity
        self._switch = switch
        self.spans: list[Span] = []
        self.dropped = 0
        self._stack: list[int] = []
        self._next_id = 1

    @property
    def on(self) -> bool:
        if self._switch is None:
            from jax.profiler import TraceAnnotation
            self._switch = TraceAnnotation.is_enabled
        return self._switch()

    def span(self, name: str, **attrs):
        """A context for one span nested inside one call, mirrored as a
        TraceAnnotation; ``NULL_SPAN`` while no capture runs."""
        if not self.on:
            return NULL_SPAN
        return _OpenSpan(self, Span(name, 0.0, 0.0, self._new_id(),
                                    attrs=attrs))

    def record(self, name: str, start: float, end: float,
               parent: Optional[int] = None, req_id: Optional[int] = None,
               **attrs) -> Optional[int]:
        """Keep a span from times already held (``time.monotonic()``
        seconds); returns its id, None while no capture runs."""
        if not self.on:
            return None
        sid = self._new_id()
        self._keep(Span(name, start, end, sid, parent, req_id, attrs))
        return sid

    def current(self) -> Optional[int]:
        """The innermost open span's id."""
        return self._stack[-1] if self._stack else None

    def outermost(self) -> Optional[int]:
        """The outermost open span's id (the engine step's, inside one)."""
        return self._stack[0] if self._stack else None

    def clear(self) -> None:
        self.spans = []
        self.dropped = 0

    def _new_id(self) -> int:
        sid = self._next_id
        self._next_id += 1
        return sid

    def _keep(self, span: Span) -> None:
        if len(self.spans) < self.capacity:
            self.spans.append(span)
        else:
            self.dropped += 1


#: The process-wide recorder the engine writes to and readers read.
SPANS = SpanRecorder()

#: What an engine on an injected (virtual) clock writes to: never on,
#: since its times are not the capture's.
NO_SPANS = SpanRecorder(capacity=0, switch=lambda: False)
