"""Scheduler: median admission wait (submit to slot) of the requests that
completed in the window, from ``EngineStats.admission_wait_s``."""

import numpy as np


def read(run):
    if not run.admission_waits:
        return None
    return float(np.median(run.admission_waits)) * 1e3
