"""Route -> Cascade -> Fallback: the host span of each admission batch
in the window (``pipeline.admit``), given to every request in it; 95th
percentile over requests (ms)."""

import numpy as np


def read(run):
    spans = run.in_window(run.spans.admits)
    if not spans:
        return None
    per_req = np.repeat([e - s for s, e, _ in spans],
                        [n for _, _, n in spans])
    return 1e3 * float(np.percentile(per_req, 95))
