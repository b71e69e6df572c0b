"""The open-loop client: sends each request when it is due, steps the
engine between sends, and times every token as it reaches the caller.

Every request is timed from the moment it was *due*, so a late send or a
stalled step counts against the system; ``late`` records how far behind
the schedule each send went. Host work is wrapped in profiler spans so
a trace can say what the host was doing while the device sat idle.
"""
from __future__ import annotations

import contextlib
import heapq
import time
from dataclasses import dataclass, field
from typing import Optional

from .traffic import Item, next_turn, turn_done


@dataclass
class Log:
    """One request as the client saw it (times in seconds from the start
    of the window)."""
    item: Item
    submit: float
    req_id: int = -1
    times: list = field(default_factory=list)
    tokens: list = field(default_factory=list)
    finished: bool = False
    handle: object = None

    @property
    def late(self) -> float:
        return self.submit - self.item.due

    @property
    def ttft(self) -> Optional[float]:
        return self.times[0] - self.item.due if self.times else None


class Client:
    """Drives one engine with one mix's traffic for one window."""

    def __init__(self, eng, mix, traffic, max_len: int, spans: bool):
        self.eng = eng
        self.max_len = max_len
        self.spans = spans
        self.logs: list[Log] = []
        self.live: list[Log] = []
        self._heap: list = []
        self._n = 0
        if mix.kind == "requests":
            for it in traffic:
                self._push(it)
            self.sessions, self.system = {}, {}
        else:
            sessions, self.system = traffic
            self.sessions = {s.sid: s for s in sessions}
            for s in sessions:
                self._next(s, s.start)

    def _push(self, item: Item) -> None:
        heapq.heappush(self._heap, (item.due, item.idx, item))

    def _next(self, sess, due: float) -> None:
        it = next_turn(sess, self.system[sess.adapter], due, self._n,
                       self.max_len - 1)
        if it is not None:
            self._n += 1
            self._push(it)

    def _span(self, name: str):
        if not self.spans:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def _submit(self, item: Item, now: float) -> None:
        from repro.core import Request

        log = Log(item, now)
        t0 = self.t0

        def on_token(_tok, times=log.times):
            times.append(time.perf_counter() - t0)

        req = Request(input_len=len(item.prompt), output_len=item.output_len,
                      adapter_id=item.adapter, prompt=item.prompt)
        log.handle = self.eng.submit(req, on_token=on_token)
        log.req_id = req.req_id
        self.logs.append(log)
        self.live.append(log)

    def _reap(self, now: float, window_s: float) -> None:
        """Settle requests that reached a terminal state; a finished
        session turn schedules its next turn a think time later."""
        still = []
        for log in self.live:
            h = log.handle
            if not h.done:
                still.append(log)
                continue
            log.tokens = h.tokens
            log.finished = h.state.name == "FINISHED"
            log.handle = None
            it = log.item
            if it.session >= 0 and log.finished:
                sess = self.sessions[it.session]
                turn_done(sess, it, log.tokens)
                due = now + sess.thinks[it.turn]
                if due < window_s:
                    self._next(sess, due)
        self.live = still

    def run(self, window_s: float, grace_s: float, on_close=None) -> float:
        """Send for ``window_s`` seconds, then keep stepping (sending
        nothing more) until every sent request ended or ``grace_s``
        passed. ``on_close`` runs once, at the close of the window.
        Returns the seconds the drain took."""
        self.t0 = time.perf_counter()
        clock = lambda: time.perf_counter() - self.t0  # noqa: E731
        window = self._span("bench_window")
        window.__enter__()
        closed = False
        while True:
            now = clock()
            if not closed and now >= window_s:
                # A step that ran past the close leaves requests due in
                # the window unsent: send them now, late, so every due
                # request is counted and timed from its due time.
                while self._heap and self._heap[0][0] < window_s:
                    self._submit(heapq.heappop(self._heap)[2], now)
                window.__exit__(None, None, None)
                closed = True
                if on_close is not None:
                    on_close()
            if closed and (not self.live or now >= window_s + grace_s):
                break
            if not closed and self._heap and self._heap[0][0] <= now:
                with self._span("submit"):
                    while self._heap and self._heap[0][0] <= now:
                        self._submit(heapq.heappop(self._heap)[2], now)
            if self.eng.busy():
                with self._span("step"):
                    self.eng.step()
                with self._span("client"):
                    self._reap(clock(), window_s)
            else:
                self._reap(now, window_s)
                nxt = self._heap[0][0] if self._heap and not closed \
                    else window_s if not closed else now + 1e-3
                with self._span("wait_arrival"):
                    time.sleep(max(0.0, min(nxt - now, 5e-3)))
        self._reap(clock(), window_s)
        for log in self.live:           # never answered within the grace
            log.tokens = log.handle.tokens
            log.handle = None
        return clock() - window_s
