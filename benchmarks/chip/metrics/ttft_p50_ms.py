"""Time to first token, median, over every request due in the window,
timed from when it was due.  A request still without a first token when
the window closes enters at its wait so far."""

import numpy as np


def read(run):
    if not run.open_loop:
        return None
    ttft = [(s.times[0] if s.times and s.times[0] <= run.w1 else run.w1)
            - s.due for s in run.served if run.w0 <= s.due < run.w1]
    return float(np.percentile(ttft, 50)) * 1e3 if ttft else None
