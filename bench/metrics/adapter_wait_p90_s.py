"""Adapter cache (``core/adapter_cache.py``): the 90th percentile of
how long a request waited for its adapter to load (the program's
``request.queued`` span's ``adapter_wait``, its ``adapter_load_wait``;
0 where the adapter was resident), over the requests whose first token
came inside the window, matched by request id."""
import numpy as np

from bench.harness import program_spans


def read(run):
    spans = program_spans.recorded()
    if spans is None:
        return None
    wait = {s.req_id: s.attrs["adapter_wait"] for s in spans
            if s.name == "request.queued"}
    waits = [wait[g.req_id] for g in run.logs
             if g.req_id in wait and g.times and g.times[0] <= run.window_s]
    return float(np.percentile(waits, 90)) if waits else None
