"""Spans inside the serving loop, switched and clocked by the profiler.

There is one switch: a JAX profiler session (``jax.profiler.trace`` or
``start_trace``, or a capture through the profiler server).  With none
active, ``span`` returns a shared no-op context after one
``TraceMe.is_enabled()`` check, and nothing is recorded.  With one
active, a span

* opens ``jax.profiler.TraceAnnotation("tryage." + name, **scalars)``,
  so it lands in the XSpace on the device operations' clock, and
* on exit appends a ``Record`` to a bounded in-memory ring, stamped
  with ``time.monotonic`` (the engine's default clock, and the clock of
  ``Request.arrival``), its parent taken from the stack of open spans
  (the serve loop is single-threaded).  Attributes set with
  ``Span.set`` (per-row arrays) stay in memory only.

The first span of each profiler session emits a zero-length
``tryage.clock`` annotation carrying ``mono_ns=time.monotonic_ns()``:
its XSpace start minus that stat is the offset from the engine clock to
the trace clock, so every record maps onto the device timeline.

While a session is active, garbage collections (``gc.callbacks``) and
XLA compilations (``jax.monitoring``) are recorded as ``gc`` and
``compile`` records under the span open at the time, so a long idle gap
of the device names its cause.

The ring is process-wide, as the profiler is.  ``records()`` reads it
and ``clear()`` empties it.
"""

from __future__ import annotations

import collections
import gc
import itertools
import time
from typing import NamedTuple

import jax
from jax._src import profiler as _jax_profiler
from jax.profiler import TraceAnnotation

PREFIX = "tryage."
RING = 2 ** 18           # a 30-s window at 1,440 req/s holds ~10k records
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_is_enabled = _jax_profiler._profiler.TraceMe.is_enabled
_profile_state = _jax_profiler._profile_state


class Record(NamedTuple):
    id: int
    name: str             # without the "tryage." prefix
    start: float          # time.monotonic seconds
    end: float
    parent: int | None    # id of the span open at the start, if any
    attrs: dict


_ring: collections.deque = collections.deque(maxlen=RING)
_stack: list = []
_ids = itertools.count(1)
_UNSYNCED = object()
_session = _UNSYNCED      # the profiler session the clock was synced in
_hooked = False
_gc_start: list = [None]


def active() -> bool:
    """Whether a profiler session is recording host spans."""
    return _is_enabled()


def records() -> list[Record]:
    return list(_ring)


def clear() -> None:
    _ring.clear()


class _NoSpan:
    """The span while no profiler session is active: records nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def __bool__(self):
        return False

    def set(self, **attrs) -> None:
        pass


NOOP = _NoSpan()


class Span:
    """One open span; ``set`` adds attributes kept in memory only."""

    __slots__ = ("name", "attrs", "start", "id", "parent", "_ann")

    def __init__(self, name: str, start: float | None, attrs: dict):
        self.name, self.start, self.attrs = name, start, attrs

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    def __enter__(self):
        if not _stack:
            _sync()
        self.id = next(_ids)
        self.parent = _stack[-1].id if _stack else None
        if self.start is None:
            self.start = time.monotonic()
        self._ann = TraceAnnotation(
            PREFIX + self.name,
            **{k: v for k, v in self.attrs.items()
               if isinstance(v, (int, float, str))})
        self._ann.__enter__()
        _stack.append(self)
        return self

    def __exit__(self, *exc):
        end = time.monotonic()
        self._ann.__exit__(*exc)
        _stack.pop()
        _ring.append(Record(self.id, self.name, self.start, end,
                            self.parent, self.attrs))
        return False


def span(name: str, start: float | None = None, **attrs):
    """A span named ``"tryage." + name`` with scalar ``attrs``, or the
    shared no-op while no profiler session is active.  ``start`` is a
    stamp the caller already took on the engine clock at the span's
    start; it is stamped here otherwise."""
    global _session
    if not _is_enabled():
        _session = _UNSYNCED
        return NOOP
    return Span(name, start, attrs)


def _record(name: str, start: float, end: float, **attrs) -> None:
    _ring.append(Record(next(_ids), name, start, end,
                        _stack[-1].id if _stack else None, attrs))


def _sync() -> None:
    """Emit ``tryage.clock`` once per profiler session, and hook the
    stall records in on the first session."""
    global _session, _hooked
    sess = _profile_state.profile_session
    if sess is _session:
        return
    _session = sess
    with TraceAnnotation(PREFIX + "clock", mono_ns=time.monotonic_ns()):
        pass
    if not _hooked:
        _hooked = True
        gc.callbacks.append(_on_gc)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        _gc_start[0] = time.monotonic() if _is_enabled() else None
    elif _gc_start[0] is not None:
        _record("gc", _gc_start[0], time.monotonic(),
                generation=info["generation"],
                collected=info["collected"])
        _gc_start[0] = None


def _on_duration(event: str, duration: float, **_kw) -> None:
    if event == COMPILE_EVENT and _is_enabled():
        now = time.monotonic()
        _record("compile", now - duration, now, seconds=duration)
