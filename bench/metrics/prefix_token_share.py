"""Prefix cache (``core/prefix_cache.py``): prompt tokens served from
cached pages over prompt tokens admitted, counted by the engine and
differenced over the window, as a percentage."""


def read(run):
    look = run.delta("prefix_lookup_tokens")
    return 100.0 * run.delta("prefix_hit_tokens") / look if look else None
