"""What several per-layer readers share: which tokens the window
produced, with the context each one saw and its adapter's rank."""
from __future__ import annotations


def decoded_tokens(run):
    """(keys attended, adapter rank) of every token the decode loop
    delivered inside the window: output token j > 0 of a request with an
    L-token prompt attended over L + j keys."""
    out = []
    for g in run.logs:
        L, rank = len(g.item.prompt), run.ranks[g.item.adapter]
        out += [(L + j, rank) for j, t in enumerate(g.times)
                if j > 0 and t <= run.window_s]
    return out


def prefills(run):
    """(cached prefix, prompt length, adapter rank) of every request
    whose first token came inside the window. The engine counts prefix
    hits only in total, so each request is given the window's share of
    its own prompt as its cached prefix."""
    hit, look = run.delta("prefix_hit_tokens"), run.delta(
        "prefix_lookup_tokens")
    share = hit / look if look else 0.0
    out = []
    for g in run.logs:
        if g.times and g.times[0] <= run.window_s:
            L = len(g.item.prompt)
            out.append((min(L - 1, round(share * L)), L,
                        run.ranks[g.item.adapter]))
    return out


def share_of_peak(flops: float, seconds: float, peak: dict):
    """Operations over time over the chip's peak, as a percentage; None
    where the trace holds no time for it."""
    if seconds <= 0:
        return None
    return 100.0 * flops / seconds / peak["flops_per_s"]
