"""Adapter cache (``core/adapter_cache.py``): the device's idle time
inside the window while the engine slept on an in-flight adapter load
(gaps whose middle lies under the program's ``engine.idle_wait`` span,
on the trace's clock), over the window; a percentage."""
from bench.harness import program_spans


def read(run):
    idle = program_spans.idle_by_span(run)
    if idle is None or run.trace.window_s <= 0:
        return None
    return 100.0 * idle.get("engine.idle_wait", 0.0) / run.trace.window_s
