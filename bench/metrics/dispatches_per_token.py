"""Engine step loop (``serving/engine.py``): decode dispatches the
engine launched (``kernels.ops.DISPATCH_METER``), over the output tokens
delivered, both inside the window."""


def read(run):
    toks = sum(1 for g in run.logs for t in g.times if t <= run.window_s)
    return run.delta("dispatches") / toks if toks else None
