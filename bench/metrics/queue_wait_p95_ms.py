"""Admission: each admitted row's wait for its admission batch (the
``tryage.admit`` span's start minus ``Request.arrival``, kept per row in
the span's in-memory record), 95th percentile over the rows of the
admissions in the window (ms)."""

import numpy as np

from bench import program_spans


def read(run):
    waits = [w for r in program_spans.records(run, "admit")
             for w in r.attrs["waits"]]
    if not waits:
        return None
    return 1e3 * float(np.nanpercentile(waits, 95))
