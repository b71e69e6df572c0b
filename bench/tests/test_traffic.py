"""The traffic generator: seeded, the same schedule for every seed, drawn
from the distributions each mix file states."""
import json
from statistics import NormalDist
from pathlib import Path

import numpy as np
import pytest

from bench.harness import traffic
from bench.tests.tiny import SESSIONS

MIXES = Path(__file__).resolve().parents[1] / "traffic"


def load(name):
    return traffic.Mix.load(MIXES / f"{name}.json")


@pytest.mark.parametrize("name", ["code-many-adapters", "code-hot-adapters"])
def test_same_seed_same_traffic(name):
    mix = load(name)
    a, b = mix.build(2**33 + 1, 30, 1000), mix.build(2**33 + 1, 30, 1000)
    assert [(i.due, i.prompt, i.output_len, i.adapter) for i in a] == \
        [(i.due, i.prompt, i.output_len, i.adapter) for i in b]


@pytest.mark.parametrize("name", ["code-many-adapters", "code-hot-adapters"])
def test_other_seed_same_schedule_other_tokens(name):
    mix = load(name)
    a, b = mix.build(1, 30, 1000), mix.build(2, 30, 1000)
    assert [(i.due, len(i.prompt), i.output_len, i.adapter) for i in a] == \
        [(i.due, len(i.prompt), i.output_len, i.adapter) for i in b]
    assert a[0].prompt != b[0].prompt
    other = traffic.Mix("o", dict(mix.spec, schedule_seed=7)).build(1, 30, 1000)
    assert [i.due for i in a] != [i.due for i in other]


@pytest.mark.parametrize("name", ["code-many-adapters", "code-hot-adapters"])
def test_short_window_replays_the_start(name):
    """Each part of the schedule is a stream of its own, so a shorter
    window sends the first requests of a longer one unchanged."""
    mix = load(name)
    short, full = mix.build(3, 10, 1000), mix.build(3, 51, 1000)
    assert 0 < len(short) < len(full)
    assert [(i.due, len(i.prompt), i.output_len, i.adapter) for i in short] \
        == [(i.due, len(i.prompt), i.output_len, i.adapter)
            for i in full[:len(short)]]


@pytest.mark.parametrize("name", ["code-many-adapters", "code-hot-adapters"])
def test_code_mix_lengths(name):
    """Splitwise's code service: prompts about 1,500 tokens at the median
    (rounded up to whole pages, clipped at the cap), answers about 13."""
    mix = load(name)
    items = mix.build(0, 8000, 10)
    plen = np.array([len(i.prompt) for i in items])
    assert abs(np.median(plen) - 1500) <= 40
    assert all(p % 16 == 0 and p <= mix.spec["prompt"]["max"] for p in plen)
    # The cap clamps the share of the lognormal above it.
    sp = mix.spec["prompt"]
    above = 1 - NormalDist().cdf(np.log(sp["max"] / sp["median"]) / sp["sigma"])
    assert abs(np.mean(plen == sp["max"]) - above) < 0.04
    assert abs(np.median([i.output_len for i in items]) - 13) <= 1
    due = [i.due for i in items]
    assert min(due) >= 0 and max(due) < 8000


def test_poisson_arrivals():
    """Exponential gaps at the mix's rate: mean 1/rate, and as many gaps
    under a fifth of the mean as the exponential puts there, so bursts
    stay in."""
    mix = load("code-many-adapters")
    rate = mix.spec["rate_per_s"]
    due = np.array([i.due for i in mix.build(3, 4000, 10)])
    gaps = np.diff(due)
    assert abs(len(due) / 4000 - rate) < 0.05 * rate
    assert abs(gaps.mean() * rate - 1) < 0.05
    assert abs(np.mean(gaps < 0.2 / rate) - (1 - np.exp(-0.2))) < 0.03


def test_power_law_popularity():
    spec = json.loads((MIXES / "code-many-adapters.json").read_text())
    ads = traffic.assign_adapters(spec["adapters"], 10000,
                                  np.random.default_rng(0))
    ranks = traffic.adapter_ranks(spec["adapters"])
    by_rank = {r: sum(1 for a in ads if ranks[a] == r) for r in set(ranks)}
    w = {r: 1 / r for r in by_rank}
    for r in by_rank:
        assert abs(by_rank[r] / 10000 - w[r] / sum(w.values())) < 0.015
    assert traffic.hot_adapters(spec["adapters"], 4)[0] in (0, 5, 10)


def test_sessions_grow_with_the_answers():
    mix = traffic.Mix("s", SESSIONS)
    sessions, system = mix.build(7, 10, 50)
    again, _ = mix.build(7, 10, 50)
    other, _ = mix.build(8, 10, 50)
    assert [s.users for s in sessions] == [s.users for s in again]
    assert [len(u) for s in sessions for u in s.users] == \
        [len(u) for s in other for u in s.users]
    s = sessions[0]
    first = traffic.next_turn(s, system[s.adapter], 0.0, 0, 1000)
    assert first.prompt[:32] == system[s.adapter]
    traffic.turn_done(s, first, [1, 2, 3])
    second = traffic.next_turn(s, system[s.adapter], 1.0, 1, 1000)
    assert second.prompt[:len(first.prompt) + 3] == first.prompt + [1, 2, 3]
    assert traffic.next_turn(s, system[s.adapter], 1.0, 2, 10) is None


def test_sessions_output_median():
    spec = dict(SESSIONS, output={"median": 129, "sigma": 0.9, "min": 1,
                                  "max": 1024})
    outs = traffic.lognormal_lengths(spec["output"], 20001,
                                     np.random.default_rng(4))
    assert abs(np.median(outs) - 129) <= 3
