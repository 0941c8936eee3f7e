"""The program's own spans (``repro.serving.tracing``), for the metric
readers: its in-memory records in the window, and its ``tryage.*`` host
events in the traced run's XSpace.  A program without them reads as
nothing (an empty list), never as an error."""

from __future__ import annotations

import os

from bench import trace

PREFIX = "tryage."
# where bench/run_cell.py has the profiler write a traced run
TRACE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".bench_out", "trace")


def records(run, name: str) -> list:
    """In-memory records called ``name`` that started and ended in the
    window (``start >= run.opened``, ``end <= run.close``)."""
    try:
        from repro.serving import tracing
    except ImportError:
        return []
    return [r for r in tracing.records() if r.name == name
            and r.start >= run.opened and r.end <= run.close]


def events(run, trace_dir: str = TRACE_DIR) -> list[trace.Event]:
    """``tryage.*`` events of the host planes of a traced run's XSpace,
    read once per run."""
    if run.trace is None:
        return []
    cached = getattr(run, "_program_events", None)
    if cached is None:
        from jax.profiler import ProfileData
        cached = []
        for plane in ProfileData.from_file(
                trace.find_xspace(trace_dir)).planes:
            if plane.name.startswith("/device:"):
                continue
            for line in plane.lines:
                cached += [trace.Event(plane.name, line.name, ev.name,
                                       float(ev.start_ns),
                                       float(ev.duration_ns),
                                       tuple((str(k), v)
                                             for k, v in ev.stats))
                           for ev in line.events
                           if ev.name.startswith(PREFIX)]
        run._program_events = cached
    return cached
