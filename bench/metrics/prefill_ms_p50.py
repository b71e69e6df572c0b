"""Model (``models/lm.py``), prompt side, on the host: the median
duration of the program's ``engine.prefill`` spans (a prefill call's
dispatch until its first tokens are on the host) that start inside the
window, on the trace's clock; milliseconds."""
import numpy as np

from bench.harness import program_spans


def read(run):
    durs = program_spans.durations_in_window(run, "engine.prefill")
    return 1e3 * float(np.median(durs)) if durs else None
