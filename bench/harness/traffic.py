"""The one traffic generator: turns a mix file into timed requests.

A mix file (``bench/traffic/<mix>.json``) holds parameters only: length
distributions, rates, adapter popularity, sessions, and the
``schedule_seed`` from which the schedule is drawn. The schedule (every
arrival, size and adapter) is one draw from that seed, replayed by every
run; the run's seed draws every token id (and, elsewhere, the weights).
So two runs differ in the system's timing, not in how much work lands
where, while the draw keeps what Poisson arrivals and heavy-tailed
lengths bring: bursts, and long prompts back to back.

The generators are copied from ``repro.serving.trace`` (Poisson
arrivals, lognormal lengths, clipped) and ``repro.core.lora`` (power law
over ranks, uniform within a rank; adapter ``i`` has rank
``ranks[i % len(ranks)]``), with the sizes each mix states. Arrivals,
prompt lengths, answer lengths and adapters each come from a stream of
their own, so a shorter window replays the start of a longer one.

Two kinds of mix:

- ``requests``: independent requests, Poisson arrivals at ``rate_per_s``;
- ``sessions``: multi-turn chats. Sessions start at ``session_rate_per_s``;
  each turn's prompt is the adapter's system prompt, the whole history
  (earlier user messages and the answers the server gave) and a new
  user message. A turn is due a think time after the previous answer
  ended, so its due time is known only while the run goes.
"""
from __future__ import annotations

import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Optional

import numpy as np


def streams(schedule_seed: int, n: int) -> list[np.random.Generator]:
    """``n`` independent generators from one schedule seed."""
    return [np.random.default_rng(s)
            for s in np.random.SeedSequence(schedule_seed).spawn(n)]


def poisson_arrivals(rate: float, window_s: float,
                     rng: np.random.Generator) -> np.ndarray:
    """Arrival times in [0, window) of a Poisson process at ``rate``
    (``serving.trace._arrival_times`` without its load spikes)."""
    times, t = [], 0.0
    while True:
        t += rng.exponential(1.0 / rate)
        if t >= window_s:
            return np.asarray(times)
        times.append(t)


def lognormal_lengths(spec: dict, n: int,
                      rng: np.random.Generator) -> np.ndarray:
    """``n`` lengths from a lognormal of the spec's ``median`` and
    ``sigma``, clipped to [``min``, ``max``] and, where the spec gives
    ``multiple_of``, rounded up to a multiple of it."""
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    x = np.clip(np.round(x), spec["min"], spec["max"]).astype(np.int64)
    m = spec.get("multiple_of", 1)
    return -(-x // m) * m


def adapter_ranks(spec: dict) -> list[int]:
    ranks = spec["ranks"]
    return [ranks[i % len(ranks)] for i in range(spec["count"])]


def adapter_popularity(spec: dict) -> np.ndarray:
    """P(adapter): P(rank) proportional to rank**-alpha, split evenly
    over the adapters of that rank."""
    ranks = adapter_ranks(spec)
    uniq = sorted(set(ranks))
    w = {r: r ** -spec["alpha"] for r in uniq}
    tot = sum(w.values())
    per = {r: w[r] / tot / ranks.count(r) for r in uniq}
    return np.array([per[r] for r in ranks])


def assign_adapters(spec: dict, n: int,
                    rng: np.random.Generator) -> np.ndarray:
    """``n`` adapter ids: a rank by the power law, then an adapter of
    that rank uniformly (``core.lora.assign_adapters``)."""
    ranks = adapter_ranks(spec)
    uniq = sorted(set(ranks))
    p = np.array([r ** -spec["alpha"] for r in uniq])
    by_rank = {r: [a for a, x in enumerate(ranks) if x == r] for r in uniq}
    out = np.empty(n, np.int64)
    for i in range(n):       # request by request: a prefix of a longer draw
        out[i] = rng.choice(by_rank[uniq[rng.choice(len(uniq),
                                                    p=p / p.sum())]])
    return out


def hot_adapters(spec: dict, k: int) -> list[int]:
    """The ``k`` most popular adapters, most popular first."""
    pop = adapter_popularity(spec)
    return [int(a) for a in np.argsort(-pop, kind="stable")[:k]]


@dataclass
class Item:
    """One request the client will send."""
    idx: int
    due: float
    prompt: list
    output_len: int
    adapter: int
    session: int = -1
    turn: int = 0


@dataclass
class Session:
    """A chat: its adapter, and per turn the user message and the
    answer length; ``history`` grows as answers come back."""
    sid: int
    start: float
    adapter: int
    users: list
    outputs: list
    thinks: list
    history: list = field(default_factory=list)
    next_turn: int = 0


class Mix:
    """A parsed mix file; ``build`` draws one seed's traffic."""

    def __init__(self, name: str, spec: dict):
        self.name = name
        self.spec = spec
        self.kind = spec["kind"]
        if self.kind not in ("requests", "sessions"):
            raise ValueError(f"mix {name}: unknown kind {self.kind!r}")

    @classmethod
    def load(cls, path: Path) -> "Mix":
        return cls(path.stem, json.loads(Path(path).read_text()))

    @property
    def adapters(self) -> dict:
        return self.spec["adapters"]

    def max_suffix(self) -> int:
        """Longest part of a prompt that is not a cached prefix: the
        whole prompt for independent requests; for a session turn, the
        previous answer and the new user message (a first turn reuses
        the system prompt)."""
        s = self.spec
        if self.kind == "requests":
            return s["prompt"]["max"]
        return s["output"]["max"] + s["user"]["max"]

    def min_suffix(self) -> int:
        s = self.spec
        if self.kind == "requests":
            return s["prompt"]["min"]
        return s["user"]["min"]

    def build(self, seed: int, window_s: float, vocab: int):
        """This seed's traffic. Returns a list of ``Item`` (requests)
        or of ``Session`` plus the per-adapter system prompts.

        The schedule is drawn from the mix's own ``schedule_seed``, so
        every run replays it; the run's ``seed`` draws every token id."""
        rng = np.random.default_rng(seed)
        s = self.spec
        if self.kind == "requests":
            r_t, r_p, r_o, r_a = streams(s["schedule_seed"], 4)
            due = poisson_arrivals(s["rate_per_s"], window_s, r_t)
            n = len(due)
            plen = lognormal_lengths(s["prompt"], n, r_p)
            olen = lognormal_lengths(s["output"], n, r_o)
            ads = assign_adapters(self.adapters, n, r_a)
            return [Item(i, float(due[i]),
                         rng.integers(0, vocab, int(plen[i])).tolist(),
                         int(olen[i]), int(ads[i])) for i in range(n)]
        r_t, r_n, r_u, r_o, r_k, r_a = streams(s["schedule_seed"], 6)
        starts = poisson_arrivals(s["session_rate_per_s"], window_s, r_t)
        n = len(starts)
        turns = r_n.integers(s["turns"]["min"], s["turns"]["max"] + 1, n)
        n_turns = int(np.sum(turns))
        users = lognormal_lengths(s["user"], n_turns, r_u)
        outs = lognormal_lengths(s["output"], n_turns, r_o)
        thinks = r_k.exponential(s["think_s"], n_turns)
        ads = assign_adapters(self.adapters, n, r_a)
        system = {a: rng.integers(0, vocab, s["system_prompt"]).tolist()
                  for a in range(self.adapters["count"])}
        out, k = [], 0
        for i in range(n):
            t = int(turns[i])
            out.append(Session(
                i, float(starts[i]), int(ads[i]),
                [rng.integers(0, vocab, int(u)).tolist()
                 for u in users[k:k + t]],
                [int(o) for o in outs[k:k + t]],
                [float(x) for x in thinks[k:k + t]]))
            k += t
        return out, system


def next_turn(sess: Session, system: list, due: float, idx: int,
              max_context: int) -> Optional[Item]:
    """The session's next turn as an ``Item`` due at ``due``, or None
    when the session is over or the turn would not fit the context."""
    t = sess.next_turn
    if t >= len(sess.users):
        return None
    prompt = system + sess.history + sess.users[t]
    if len(prompt) + sess.outputs[t] >= max_context:
        return None
    sess.next_turn += 1
    return Item(idx, due, prompt, sess.outputs[t], sess.adapter,
                session=sess.sid, turn=t)


def turn_done(sess: Session, item: Item, answer: list) -> None:
    """Append the turn's user message and the answer the client got."""
    sess.history = sess.history + sess.users[item.turn] + list(answer)

