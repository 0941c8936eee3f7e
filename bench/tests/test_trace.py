"""The trace reduction, on a hand-made trace and on a trimmed trace
recorded on a TPU v5e (``bench/testdata/``)."""

import gzip
import json
import os

import pytest

from bench import trace

DEV = trace.DEVICE_PREFIX + "0"
HOST = "/host:CPU"
TESTDATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata")


def ev(plane, name, start, dur, **stats):
    line = trace.OPS_LINE if plane == DEV else "python"
    return trace.Event(plane, line, name, float(start), float(dur),
                       tuple(stats.items()))


def hand_made():
    return [
        ev(HOST, "bench.window", 0, 1000),
        ev(HOST, "bench.admit", 100, 100, rows=3, bucket=4),
        ev(HOST, "bench.flush", 300, 300, expert=1, rows=2, bucket=2),
        ev(HOST, "bench.wait", 700, 200),
        ev(DEV, "fusion.1", 120, 30),
        ev(DEV, "%router_score_fused.1 = (f32[8,11]) custom-call(f32[8,128]), custom_call_target=\"tpu_custom_call\"", 140, 20),
        ev(DEV, "convolution.7", 320, 100),
        ev(DEV, "convolution.7", 450, 100),
        ev(DEV, "copy.2", 950, 100),            # runs past the window
    ]


def test_busy_union_idle_share_and_attribution():
    r = trace.Reduction(hand_made())
    # busy: [120,160) + [320,420) + [450,550) + [950,1000) = 290 ns
    assert r.busy_s() == pytest.approx(290e-9)
    assert r.window_s == pytest.approx(1000e-9)
    assert r.idle_share() == pytest.approx(0.71)
    assert [trace.op_name(e.name) for e in r.ops_in("bench.admit")] == [
        "fusion.1", "%router_score_fused.1"]
    assert r.device_s(r.ops_in("bench.flush")) == pytest.approx(200e-9)
    assert r.spans_named("bench.flush")[0].stat("rows") == 2


def test_breakdown_names_gaps_by_host_span():
    b = trace.Reduction(hand_made()).breakdown()
    assert b["device_ops"][0] == ["flush/convolution.7", pytest.approx(200e-9)]
    gaps = {tuple((n, round(s * 1e9))) for n, s in b["idle_gaps"]}
    # [550,950) midpoint 750 in wait; [160,320) midpoint 240 is host;
    # [0,120) midpoint 60 host; [420,450) in flush
    assert ("wait", 400) in gaps and ("host", 160) in gaps
    assert ("host", 120) in gaps and ("flush", 30) in gaps
    assert len(b["idle_gaps"]) <= 10 and len(b["device_ops"]) <= 10


def test_no_device_ops_reads_nothing():
    r = trace.Reduction([e for e in hand_made() if e.plane == HOST])
    assert r.idle_share() is None and r.busy_s() == 0.0


def recorded():
    """The first 200 ms of a traced ``paper11-steady`` window on a TPU v5e
    (``bench/testdata``): its device operations and benchmark spans as
    ``trace.load`` keeps them, the window cut to those 200 ms."""
    path = os.path.join(TESTDATA, "v5e_paper11_steady_200ms.json.gz")
    with gzip.open(path, "rt") as f:
        rows = json.load(f)
    return [trace.Event(p, line, n, s, d, tuple(map(tuple, st)))
            for p, line, n, s, d, st in rows]


def sweep_busy_ns(events, lo, hi):
    """Busy time by a sweep over sorted interval edges, independent of
    ``trace.union``."""
    edges = []
    for e in events:
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        if t > s:
            edges += [(s, 1), (t, -1)]
    edges.sort(key=lambda x: (x[0], -x[1]))
    busy, depth, since = 0.0, 0, None
    for t, step in edges:
        if depth == 0 and step == 1:
            since = t
        depth += step
        if depth == 0:
            busy += t - since
    return busy


def test_recorded_chip_trace():
    events = recorded()
    r = trace.Reduction(events)
    assert r.devices == ["/device:TPU:0"]
    dev = [e for e in events if e.plane == "/device:TPU:0"]
    assert r.busy_s() == pytest.approx(
        sweep_busy_ns(dev, r.lo, r.hi) * 1e-9, rel=1e-9)
    assert 0.5 < r.idle_share() < 1.0
    admits = r.spans_named("bench.admit")
    flushes = r.spans_named("bench.flush")
    assert len(admits) >= 5 and len(flushes) >= 10
    # every admission ran its decision step on the device
    assert r.device_s(r.ops_in("bench.admit")) > 0
    for a in admits:
        assert 1 <= a.stat("rows") <= a.stat("bucket") <= 256
    # every flush ran device work; flush spans carry expert, rows, bucket
    in_flush = r.ops_in("bench.flush")
    assert r.device_s(in_flush) > 0
    for s in flushes:
        assert 1 <= s.stat("rows") <= s.stat("bucket") <= 128
        assert 0 <= s.stat("expert") < 11
    b = r.breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    # an expert step's layer loop takes the most device time
    assert b["device_ops"][0][0] == "flush/%while.3"
