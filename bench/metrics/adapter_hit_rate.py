"""Adapter cache (``core/adapter_cache.py``): lookups that found the
adapter resident, over all lookups, counted by the cache and
differenced over the window, as a percentage."""


def read(run):
    hits, misses = run.delta("hits"), run.delta("misses")
    return 100.0 * hits / (hits + misses) if hits + misses else None
