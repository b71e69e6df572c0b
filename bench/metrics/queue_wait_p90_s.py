"""Scheduler (``core/scheduler.py``): the 90th percentile of the
program's own queue wait (``RequestRecord.queue_wait``, arrival to first
admission on the engine's clock) of the requests whose first token came
inside the window."""
import numpy as np


def read(run):
    waits = [run.queue_wait[g.req_id] for g in run.logs
             if g.req_id in run.queue_wait and g.times
             and g.times[0] <= run.window_s]
    return float(np.percentile(waits, 90)) if waits else None
