"""Execute: device idle time inside the program's ``tryage.flush`` spans
of the traced run (their union in the window, less its overlap with the
device's busy intervals, averaged over the chips used), over the traced
window (%)."""

import bisect

from bench import program_spans, trace


def read(run):
    tr = run.trace
    if tr is None or not tr.devices:
        return None
    spans = trace.union(
        (max(e.start_ns, tr.lo), min(e.end_ns, tr.hi))
        for e in program_spans.events(run)
        if e.name == program_spans.PREFIX + "flush"
        and e.end_ns > tr.lo and e.start_ns < tr.hi)
    if not spans:
        return None
    idle = 0.0
    for busy in tr.busy.values():          # sorted, disjoint
        starts = [s for s, _ in busy]
        for s, e in spans:
            i = max(bisect.bisect_right(starts, s) - 1, 0)
            covered = 0.0
            while i < len(busy) and busy[i][0] < e:
                covered += max(0.0, min(busy[i][1], e) - max(busy[i][0], s))
                i += 1
            idle += (e - s) - covered
    return 100.0 * idle / len(tr.busy) * 1e-9 / tr.window_s
