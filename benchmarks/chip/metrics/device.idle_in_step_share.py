"""Device: idle device time inside pump spans (the engine step's host
work: dispatch, readback, bookkeeping), as a share of the traced window."""


def read(run):
    t = run.trace
    if t is None:
        return None
    return 100.0 * t.idle_in_pumps_s / t.window_s
