"""Execute: host time of a flush, its ``tryage.flush`` span less its
``tryage.flush.device`` child (the wait for the expert step's outputs),
mean over the flushes in the window (ms)."""

from bench import program_spans


def read(run):
    flushes = program_spans.records(run, "flush")
    if not flushes:
        return None
    device = {r.parent: r.end - r.start
              for r in program_spans.records(run, "flush.device")}
    host = [r.end - r.start - device.get(r.id, 0.0) for r in flushes]
    return 1e3 * sum(host) / len(host)
