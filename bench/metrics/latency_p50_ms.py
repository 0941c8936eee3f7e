"""Median over every request due in the window of due time to the
moment ``serve()`` yields its Result (host clock, ms)."""

import numpy as np


def read(run):
    return 1e3 * float(np.percentile(run.latency_s, 50))
