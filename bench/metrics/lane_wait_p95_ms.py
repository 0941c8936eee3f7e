"""Lanes: each flushed row's wait in its expert lane (the
``tryage.flush`` span's start minus the row's ``LaneEntry.pushed``,
kept per row in the span's in-memory record), 95th percentile over the
rows of the flushes in the window (ms)."""

import numpy as np

from bench import program_spans


def read(run):
    waits = [w for r in program_spans.records(run, "flush")
             for w in r.attrs["waits"]]
    if not waits:
        return None
    return 1e3 * float(np.nanpercentile(waits, 95))
