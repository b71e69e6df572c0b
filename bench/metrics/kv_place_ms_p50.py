"""Engine step loop (``serving/engine.py``): the median duration of the
program's ``engine.kv_place`` spans (writing a prefill's K/V into its
pages and seating its rows) that start inside the window, on the
trace's clock; milliseconds."""
import numpy as np

from bench.harness import program_spans


def read(run):
    durs = program_spans.durations_in_window(run, "engine.kv_place")
    return 1e3 * float(np.median(durs)) if durs else None
