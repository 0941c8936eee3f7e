"""Reduction of a profiler trace to device metrics.

The traced run writes one XSpace (``*.xplane.pb``) with the device's
planes and the host's.  ``load`` keeps what the metrics read, as plain
``Event``s: the device's operations, and the benchmark's own host spans
(``jax.profiler.TraceAnnotation`` events named ``bench.*``: ``window``
around the traced window, ``admit`` around each admission batch,
``flush`` around each lane flush, ``wait`` around the feed's sleeps),
which the profiler puts on the device's clock.

``Reduction`` then gives
- the union of the device's busy intervals inside the window, its busy
  seconds and idle share;
- the device time spent inside each kind of host span (the expert steps
  run inside ``flush``, the decision step inside ``admit``, since both
  block on their results);
- the ``breakdown``: the device operations that took most time, and the
  longest idle gaps, each named by the benchmark span the host was in.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


def op_name(name: str) -> str:
    """The HLO instruction's name without its text ("%fusion.175")."""
    return name.split(" = ", 1)[0]


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    stats: tuple = ()

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    def stat(self, key, default=None):
        return dict(self.stats).get(key, default)


def find_xspace(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"{trace_dir}: expected one .xplane.pb, "
                                f"found {len(paths)}")
    return paths[0]


def options():
    """Profiler options for the traced run: host spans at level 1 (the
    ``TraceAnnotation``s) and no Python tracer, whose per-call events
    would slow the serve loop it is measuring."""
    import jax
    o = jax.profiler.ProfileOptions()
    o.host_tracer_level = 1
    o.python_tracer_level = 0
    return o


def load(path: str) -> list[Event]:
    """Device operations and ``bench.*`` host spans of one XSpace."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                if device or ev.name.startswith(SPAN_PREFIX):
                    out.append(Event(plane.name, line.name, ev.name,
                                     float(ev.start_ns),
                                     float(ev.duration_ns),
                                     tuple((str(k), v) for k, v in ev.stats)))
    return out


def union(intervals) -> list[tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


class Reduction:
    """Device metrics of one traced window (see the module docstring)."""

    def __init__(self, events: list[Event]):
        spans = [e for e in events if e.name.startswith(SPAN_PREFIX)]
        windows = [e for e in spans if e.name == "bench.window"]
        if len(windows) != 1:
            raise ValueError(f"expected one bench.window span, found "
                             f"{len(windows)}")
        self.lo, self.hi = windows[0].start_ns, windows[0].end_ns
        self.spans = sorted((e for e in spans if e.name != "bench.window"),
                            key=lambda e: e.start_ns)
        self._starts = [e.start_ns for e in self.spans]
        ops = [e for e in events if e.plane.startswith(DEVICE_PREFIX)
               and e.end_ns > self.lo and e.start_ns < self.hi]
        self.devices = sorted({e.plane for e in ops})
        self.ops = ops
        self._owner_idx = self._owners(ops)
        self._owner = [self.spans[j].name if j >= 0 else None
                       for j in self._owner_idx]
        self.busy = {d: union(_clip([(e.start_ns, e.end_ns) for e in ops
                                     if e.plane == d], self.lo, self.hi))
                     for d in self.devices}

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices that ran anything."""
        if not self.devices:
            return 0.0
        return sum(_length(b) for b in self.busy.values()) \
            / len(self.devices) * 1e-9

    def idle_share(self) -> float | None:
        if not self.devices:
            return None
        return 1.0 - self.busy_s() / self.window_s

    def span_at(self, t_ns: float) -> Event | None:
        """The innermost benchmark span open at ``t_ns``."""
        i = bisect.bisect_right(self._starts, t_ns)
        best = None
        for e in reversed(self.spans[max(0, i - 64):i]):
            if e.start_ns <= t_ns < e.end_ns and (
                    best is None or e.start_ns > best.start_ns):
                best = e
        return best

    def _owners(self, ops: list[Event]) -> list[int]:
        """Index of the span open at each op's start (-1: none): the
        latest span that started at or before it, if it has not ended
        (the benchmark's spans do not nest)."""
        import numpy as np
        if not self.spans or not ops:
            return [-1] * len(ops)
        starts = np.array(self._starts)
        ends = np.array([e.end_ns for e in self.spans])
        t = np.array([e.start_ns for e in ops])
        i = np.searchsorted(starts, t, side="right") - 1
        inside = (i >= 0) & (t < ends[np.maximum(i, 0)])
        return np.where(inside, i, -1).tolist()

    def ops_in(self, span_name: str) -> list[Event]:
        """Device operations that started inside a span of that name."""
        return [e for e, o in zip(self.ops, self._owner) if o == span_name]

    def spans_named(self, span_name: str) -> list[Event]:
        return [e for e in self.spans if e.name == span_name
                and e.start_ns >= self.lo and e.end_ns <= self.hi]

    def device_s(self, ops: list[Event]) -> float:
        """Busy seconds of ``ops`` (their interval union per device,
        summed over devices)."""
        total = 0.0
        for d in {e.plane for e in ops}:
            total += _length(union((e.start_ns, e.end_ns) for e in ops
                                   if e.plane == d))
        return total * 1e-9

    def breakdown(self, top: int = 10, label=None) -> dict:
        """Top device operations by time, each named "<span>/<op>" after
        the host span it ran in (``label(span)`` names a span, by
        default its name without the prefix), and the longest idle gaps
        named by the span open at their midpoint."""
        label = label or (lambda s: s.name[len(SPAN_PREFIX):])
        per_op = collections.Counter()
        for e, j in zip(self.ops, self._owner_idx):
            where = label(self.spans[j]) if j >= 0 else "host"
            per_op[f"{where}/{op_name(e.name)}"] += (
                min(e.end_ns, self.hi) - max(e.start_ns, self.lo)) * 1e-9
        gaps = []
        for d, busy in self.busy.items():
            edges = [self.lo] + [t for iv in busy for t in iv] + [self.hi]
            for s, e in zip(edges[::2], edges[1::2]):
                if e > s:
                    gaps.append((e - s, s, e))
        gaps.sort(reverse=True)
        named = []
        for length, s, e in gaps[:top]:
            span = self.span_at((s + e) / 2)
            named.append([span.name[len(SPAN_PREFIX):] if span else "host",
                          length * 1e-9])
        return {"device_ops": [[k, v] for k, v in per_op.most_common(top)],
                "idle_gaps": named}

    def idle_by_span(self) -> dict:
        """Idle seconds in the window by the benchmark span open at each
        gap's midpoint (gaps split at span edges are not split here)."""
        out = collections.Counter()
        for busy in self.busy.values():
            edges = [self.lo] + [t for iv in busy for t in iv] + [self.hi]
            for s, e in zip(edges[::2], edges[1::2]):
                if e > s:
                    span = self.span_at((s + e) / 2)
                    out[span.name[len(SPAN_PREFIX):] if span
                        else "host"] += (e - s) * 1e-9
        return dict(out)
