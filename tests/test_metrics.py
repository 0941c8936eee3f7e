"""Metrics export: exposition-format correctness of ``render()``, the
registry contract, and the scrape endpoint round-trip.

``render`` duck-types its stats argument, so most tests run on a plain
fake; one test renders a real ``EngineStats`` to catch field renames.
"""

import urllib.error
import urllib.request

import pytest

from repro.serving.metrics import (CONTENT_TYPE, LATENCY_BUCKETS, METRICS,
                                   MetricsServer, metric_names, render)


class FakeStats:
    """The attribute surface ``render`` reads, with overridable values."""

    def __init__(self, **kw):
        self.served = 7
        self.per_expert = {"small": 4, "big": 3}
        self.admitted = 9
        self.shed = 2
        self.shed_by_priority = {0: 2}
        self.failed = 1
        self.cache_hits = 3
        self.cache_misses = 4
        self.cache_tier_hits = {"t1": 2, "t2": 1}
        self.cache_revalidations = 1
        self.cache_revalidation_rejects = 0
        self.cache_key_dropped_lambda = 0
        self.escalations = 1
        self.cascade_depth_hist = {1: 1}
        self.spec_launched = 4
        self.spec_hits = 2
        self.spec_cancelled = 1
        self.spec_wasted = 1
        self.spec_wasted_tokens = 32
        self.fallbacks = 2
        self.fallback_depth_hist = {1: 2}
        self.degraded = 0
        self.reroutes = 1
        self.expert_failures = {"big": 1}
        self.flushes = {"target": 2, "deadline": 1}
        self.padded_rows = 5
        self.flush_round_trips = 3
        self.total_flops = 1.5e9
        self.router_time_s = 0.25
        self.expert_time_s = 1.5
        self.adapt_updates = 0
        self.feedback_events = 7
        self.router_version = 1
        self.replay_len = 7
        self.sessions = 2
        self.admission_queue_peak = 3
        self.latencies = [0.002, 0.004, 0.03, 0.2]
        self.queue_waits = [0.001, 0.02]
        self.lane_waits = [0.003, 0.04, 0.06]
        for k, v in kw.items():
            setattr(self, k, v)


class FakeHealth:
    def __init__(self, n):
        self.n = n
        self.states = [type("S", (), {"depth_ewma": 1.5 * i,
                                      "latency_ewma_s": 0.01 * i,
                                      "failure_ewma": 0.0})()
                       for i in range(n)]

    def healthy(self, i):
        return i != 1

    def available(self, i):
        return i == 0


def _families(text):
    """Parse exposition text into {family: (mtype, [sample lines])},
    asserting the format invariants along the way: HELP then TYPE then
    that family's samples, contiguous, nothing stray."""
    fams, current = {}, None
    lines = text.splitlines()
    assert text.endswith("\n") and lines
    i = 0
    while i < len(lines):
        line = lines[i]
        assert line.startswith("# HELP "), f"expected HELP at: {line!r}"
        name = line.split()[2]
        tline = lines[i + 1]
        assert tline.startswith(f"# TYPE {name} "), tline
        mtype = tline.split()[3]
        assert mtype in ("counter", "gauge", "histogram")
        i += 2
        samples = []
        while i < len(lines) and not lines[i].startswith("#"):
            base = lines[i].split("{")[0].split(" ")[0]
            if mtype == "histogram":
                assert base in (name + "_bucket", name + "_sum",
                                name + "_count"), lines[i]
            else:
                assert base == name, lines[i]
            samples.append(lines[i])
            i += 1
        assert name not in fams, f"duplicate family {name}"
        fams[name] = (mtype, samples)
    return fams


def test_registry_names_unique_and_prefixed():
    names = metric_names()
    assert len(names) == len(set(names)) == len(METRICS)
    assert all(n.startswith("tryage_") for n in names)
    for m in METRICS:
        assert (m.mtype == "counter") == m.name.endswith("_total")


def test_render_covers_whole_registry_in_order():
    fams = _families(render(FakeStats()))
    assert list(fams) == metric_names()
    for m in METRICS:
        assert fams[m.name][0] == m.mtype


def test_scalar_and_labelled_samples():
    fams = _families(render(FakeStats()))
    assert fams["tryage_requests_served_total"][1] == \
        ["tryage_requests_served_total 7"]
    by_expert = fams["tryage_requests_by_expert_total"][1]
    assert 'tryage_requests_by_expert_total{expert="big"} 3' in by_expert
    assert 'tryage_requests_by_expert_total{expert="small"} 4' in by_expert
    assert by_expert == sorted(by_expert)      # deterministic label order
    assert fams["tryage_flushes_total"][1] == \
        ['tryage_flushes_total{reason="deadline"} 1',
         'tryage_flushes_total{reason="target"} 2']


def test_label_values_escaped():
    stats = FakeStats(per_expert={'we"ird\\name': 1})
    out = render(stats)
    assert r'{expert="we\"ird\\name"} 1' in out


def test_histogram_buckets_monotone_and_consistent():
    lat = [0.002, 0.004, 0.03, 0.2]
    fams = _families(render(FakeStats(latencies=lat)))
    samples = fams["tryage_request_latency_seconds"][1]
    buckets = [s for s in samples if "_bucket" in s]
    assert len(buckets) == len(LATENCY_BUCKETS) + 1
    counts = [float(s.rsplit(" ", 1)[1]) for s in buckets]
    assert counts == sorted(counts)            # cumulative => monotone
    assert counts[-1] == len(lat)              # +Inf holds everything
    # spot-check: two latencies at or under 5ms
    assert 'le="0.005"} 2' in buckets[1]
    total = [s for s in samples if s.startswith(
        "tryage_request_latency_seconds_count")][0]
    assert total.endswith(f" {len(lat)}")
    ssum = [s for s in samples if s.startswith(
        "tryage_request_latency_seconds_sum")][0]
    assert float(ssum.rsplit(" ", 1)[1]) == pytest.approx(sum(lat))


def test_histogram_empty_window():
    fams = _families(render(FakeStats(latencies=[])))
    samples = fams["tryage_request_latency_seconds"][1]
    for s in samples:
        assert s.endswith(" 0")


def test_health_series_headers_only_without_health():
    fams = _families(render(FakeStats()))
    for name in ("tryage_expert_healthy", "tryage_expert_available",
                 "tryage_expert_failure_ewma"):
        assert fams[name][1] == []             # present but empty


def test_health_series_with_names():
    fams = _families(render(FakeStats(), FakeHealth(3), ["s", "m", "b"]))
    assert fams["tryage_expert_healthy"][1] == \
        ['tryage_expert_healthy{expert="s"} 1',
         'tryage_expert_healthy{expert="m"} 0',
         'tryage_expert_healthy{expert="b"} 1']
    assert fams["tryage_expert_available"][1][0].endswith(" 1")
    assert fams["tryage_expert_available"][1][1].endswith(" 0")
    assert fams["tryage_expert_lane_depth_ewma"][1] == \
        ['tryage_expert_lane_depth_ewma{expert="s"} 0',
         'tryage_expert_lane_depth_ewma{expert="m"} 1.5',
         'tryage_expert_lane_depth_ewma{expert="b"} 3']


def test_render_real_engine_stats():
    """Field-rename canary: render a real (default) EngineStats."""
    from repro.serving.engine import EngineStats
    fams = _families(render(EngineStats()))
    assert list(fams) == metric_names()
    assert fams["tryage_requests_served_total"][1] == \
        ["tryage_requests_served_total 0"]


# ------------------------------------------------------ scrape endpoint


def test_metrics_server_round_trip():
    stats = FakeStats()
    srv = MetricsServer(0, lambda: render(stats)).start()
    try:
        url = f"http://127.0.0.1:{srv.port}/metrics"
        with urllib.request.urlopen(url, timeout=5) as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"] == CONTENT_TYPE
            body = resp.read().decode("utf-8")
        assert list(_families(body)) == metric_names()
        # a fresh collect() per scrape: mutate and re-read
        stats.served = 99
        with urllib.request.urlopen(url, timeout=5) as resp:
            assert "tryage_requests_served_total 99" in \
                resp.read().decode("utf-8")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/nope", timeout=5)
        assert ei.value.code == 404
    finally:
        srv.stop()
