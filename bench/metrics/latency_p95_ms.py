"""95th percentile over every request due in the window of due time to
the moment ``serve()`` yields its Result (host clock, ms); failed
Results count, a request with no Result counts until the run's end."""

import numpy as np


def read(run):
    return 1e3 * float(np.percentile(run.latency_s, 95))
