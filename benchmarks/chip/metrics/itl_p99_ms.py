"""Gap between consecutive tokens of a request as the client receives
them, 99th percentile over every gap that ends in the window."""

import numpy as np


def read(run):
    gaps = [b - a for s in run.served
            for a, b in zip(s.times, s.times[1:]) if run.w0 <= b <= run.w1]
    return float(np.percentile(gaps, 99)) * 1e3 if gaps else None
