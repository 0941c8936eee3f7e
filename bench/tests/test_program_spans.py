"""The readers of the program's spans, on hand-made records and events,
and ``program_spans.events`` on an XSpace the profiler wrote here."""

import types

import jax
import pytest

from bench import harness, program_spans, trace
from repro.serving import tracing

DEV = trace.DEVICE_PREFIX + "0"
HOST = "/host:CPU"
WINDOW = (10.0, 20.0)            # run.opened, run.close (engine clock)


def rec(id, name, start, end, parent=None, **attrs):
    return tracing.Record(id, name, start, end, parent, attrs)


def records():
    return [
        # before the window: left out
        rec(1, "admit", 9.0, 9.5, waits=[9.0]),
        rec(2, "admit", 11.0, 11.002, uids=[0, 1], waits=[0.001, 0.003]),
        rec(3, "admit", 12.0, 12.002, uids=[2], waits=[0.02]),
        rec(4, "flush", 13.0, 13.010, uids=[0, 1], waits=[0.004, 0.006]),
        rec(5, "flush.device", 13.002, 13.008, parent=4),
        rec(6, "flush", 14.0, 14.004, uids=[2], waits=[0.05]),
        rec(7, "flush.device", 14.001, 14.002, parent=6),
        # open past the close: left out
        rec(8, "flush", 19.99, 20.5, uids=[3], waits=[9.0]),
    ]


def ev(plane, name, start, dur, **stats):
    line = trace.OPS_LINE if plane == DEV else "python"
    return trace.Event(plane, line, name, float(start), float(dur),
                       tuple(stats.items()))


def reduction():
    return trace.Reduction([
        ev(HOST, "bench.window", 0, 1000),
        ev(DEV, "fusion.1", 120, 30),
        ev(DEV, "convolution.7", 320, 100),
        ev(DEV, "convolution.7", 450, 100),
    ])


def events():
    return [
        ev(HOST, "tryage.clock", 0, 0, mono_ns=0),
        # [300, 600): busy [320, 420) + [450, 550), idle 100
        ev(HOST, "tryage.flush", 300, 300, flush_id=0),
        # [900, 1100) cut at the window's close: idle 100
        ev(HOST, "tryage.flush", 900, 200, flush_id=1),
        ev(HOST, "tryage.flush.device", 310, 250),
    ]


def fake_run(traced=True):
    return types.SimpleNamespace(opened=WINDOW[0], close=WINDOW[1],
                                 trace=reduction() if traced else None)


@pytest.fixture
def hand_made(monkeypatch):
    monkeypatch.setattr(tracing, "records", records)
    monkeypatch.setattr(program_spans, "events", lambda run: events())


def test_records_in_window_only(hand_made):
    got = program_spans.records(fake_run(), "flush")
    assert [r.id for r in got] == [4, 6]


def test_queue_and_lane_wait_p95(hand_made):
    # rows 1, 3, 20 ms: the 95th percentile interpolates at rank 1.9
    q = harness.reader("queue_wait_p95_ms")(fake_run())
    assert q == pytest.approx(3.0 + 0.9 * 17.0)
    # rows 4, 6, 50 ms
    lane = harness.reader("lane_wait_p95_ms")(fake_run())
    assert lane == pytest.approx(6.0 + 0.9 * 44.0)


def test_flush_host_ms(hand_made):
    # (10 - 6) ms and (4 - 1) ms of host time
    assert harness.reader("flush_host_ms")(fake_run()) == pytest.approx(3.5)


def test_flush_idle_share(hand_made):
    # 200 ns idle inside flushes over a 1000-ns window
    assert harness.reader("flush_idle_share")(fake_run()) == \
        pytest.approx(20.0)


@pytest.mark.parametrize("metric", ["queue_wait_p95_ms", "lane_wait_p95_ms",
                                    "flush_host_ms", "flush_idle_share"])
def test_nothing_recorded_reads_none(monkeypatch, metric):
    monkeypatch.setattr(tracing, "records", lambda: [])
    monkeypatch.setattr(program_spans, "events", lambda run: [])
    assert harness.reader(metric)(fake_run()) is None
    assert harness.reader(metric)(fake_run(traced=False)) is None


def test_events_read_from_xspace(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with tracing.span("flush", flush_id=7, rows=2):
            pass
    tracing.clear()
    run = fake_run()
    got = program_spans.events(run, str(tmp_path))
    assert {e.name for e in got} == {"tryage.clock", "tryage.flush"}
    (f,) = [e for e in got if e.name == "tryage.flush"]
    assert f.stat("flush_id") == 7 and f.stat("rows") == 2
    assert program_spans.events(run, "/nonexistent") is got   # read once
    assert program_spans.events(fake_run(traced=False), str(tmp_path)) == []
