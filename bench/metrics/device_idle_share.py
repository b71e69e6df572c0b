"""Device: the part of the traced window in which no operation ran on
the chip (1 - the union of operation intervals over the window),
averaged over the chips used; a percentage."""


def read(run):
    if run.trace is None or not run.trace.planes or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.mean_busy_s() / run.trace.window_s)
