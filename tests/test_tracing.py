"""The serving loop's spans (``repro.serving.tracing``): off without a
profiler session, nested records and per-row waits under one, their
XSpace mirrors on the trace clock, stall records, and the wait
histograms that share their stamps."""

import gc
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.objective import recency_constraint, size_constraint
from repro.core.router import RouterConfig, init_router
from repro.data.batching import mlm_batch
from repro.serving import Request, TryageEngine, tracing
from repro.serving.metrics import render

RC = RouterConfig(n_models=3, vocab_size=64, num_layers=1, d_model=32,
                  num_heads=2, d_ff=64)
ADMIT_CHILDREN = {"admit.cache", "admit.dispatch", "admit.device",
                  "admit.cascade"}
FLUSH_CHILDREN = {"flush.pad", "flush.dispatch", "flush.device",
                  "flush.fetch", "flush.results", "flush.feedback"}


def _engine(library, **kw):
    rp, _ = init_router(jax.random.PRNGKey(9), RC, uncertainty=True)
    cons = [size_constraint(library), recency_constraint(library)]
    kw.setdefault("max_batch", 8)
    kw.setdefault("lane_target", 4)
    return TryageEngine(library, rp, RC, cons, **kw)


def _requests(n, seed=0, thresholds=(0.0,)):
    rng = np.random.default_rng(seed)
    toks = rng.integers(4, 64, size=(n, 32)).astype(np.int32)
    mb = mlm_batch(toks, rng, 0.2, 64)
    mix = [{}, {"size": 1.0}, {"size": 8.0}, {"recency": 2.0}]
    return [Request(uid=i, tokens=mb["tokens"][i], targets=mb["targets"][i],
                    mask=mb["mask"][i], lambdas=mix[i % len(mix)],
                    min_confidence=thresholds[i % len(thresholds)])
            for i in range(n)]


def _serve(eng, reqs):
    return list(eng.serve(iter(reqs)))


def _traced(eng, reqs, trace_dir):
    """Serve under a profiler session; also returns every flushed
    entry's ``pushed`` stamp, by uid, as the engine's own flush saw it."""
    flush = eng.pipeline.flush
    pushed = {}

    def spy(expert_idx, entries, reason):
        pushed.update((en.req.uid, en.pushed) for en in entries)
        return flush(expert_idx, entries, reason)

    eng.pipeline.flush = spy
    tracing.clear()
    with jax.profiler.trace(str(trace_dir)):
        results = _serve(eng, reqs)
    recs = tracing.records()
    tracing.clear()
    return results, recs, pushed


@pytest.fixture(scope="module")
def traced(tiny_library, tmp_path_factory):
    eng = _engine(tiny_library, use_kernel=True)
    reqs = _requests(21, seed=1)
    trace_dir = tmp_path_factory.mktemp("trace")
    results, recs, pushed = _traced(eng, reqs, trace_dir)
    return eng, reqs, results, recs, pushed, trace_dir


def test_off_records_nothing_and_returns_the_shared_noop(tiny_library):
    assert not tracing.active()
    assert tracing.span("flush", rows=3) is tracing.NOOP
    tracing.clear()
    eng = _engine(tiny_library)
    assert len(_serve(eng, _requests(9))) == 9
    assert tracing.records() == []
    # the operator histograms do not need the profiler
    assert len(eng.stats.queue_waits) == 9
    assert len(eng.stats.lane_waits) == 9


def test_admit_and_flush_records_nest_their_children(traced):
    _, _, _, recs, _, _ = traced
    by_id = {r.id: r for r in recs}
    for root, children in (("admit", ADMIT_CHILDREN),
                           ("flush", FLUSH_CHILDREN)):
        roots = [r for r in recs if r.name == root]
        assert roots
        for r in roots:
            assert r.parent is None
            kids = [c for c in recs if c.parent == r.id]
            assert {c.name for c in kids} == children
            for c in kids:
                assert r.start <= c.start <= c.end <= r.end
    for r in recs:
        if r.parent is not None:
            p = by_id[r.parent]
            assert p.start <= r.start <= r.end <= p.end
    pushes = [r for r in recs if r.name == "lanes.push"]
    admits = [r for r in recs if r.name == "admit"]
    assert len(pushes) == len(admits)
    assert sum(r.attrs["rows"] for r in pushes) == 21


def test_per_row_waits_are_the_stamps(traced):
    eng, reqs, _, recs, pushed, _ = traced
    admits = [r for r in recs if r.name == "admit"]
    flushes = [r for r in recs if r.name == "flush"]
    push_starts = {r.start for r in recs if r.name == "lanes.push"}
    queue, lane = [], []
    for r in admits:
        assert r.attrs["rows"] == len(r.attrs["uids"])
        want = [r.start - reqs[u].arrival for u in r.attrs["uids"]]
        assert r.attrs["waits"] == want
        queue += want
    for r in flushes:
        want = [r.start - pushed[u] for u in r.attrs["uids"]]
        assert r.attrs["waits"] == want
        assert all(pushed[u] in push_starts for u in r.attrs["uids"])
        lane += want
    assert min(queue) >= 0 and min(lane) >= 0
    assert list(eng.stats.queue_waits) == queue
    assert list(eng.stats.lane_waits) == lane


def test_every_served_uid_in_exactly_one_flush(traced):
    _, _, results, recs, _, _ = traced
    uids = [u for r in recs if r.name == "flush" for u in r.attrs["uids"]]
    assert sorted(uids) == sorted(r.uid for r in results) == list(range(21))
    ids = [r.attrs["flush_id"] for r in recs if r.name == "flush"]
    assert len(set(ids)) == len(ids)


def test_each_flush_record_counts_one_round_trip(traced):
    eng, _, _, recs, _, _ = traced
    flushes = [r for r in recs if r.name == "flush"]
    assert [r.attrs["round_trips"] for r in flushes] == [1] * len(flushes)
    assert eng.stats.flush_round_trips == len(flushes)


def test_xspace_mirrors_each_flush_on_the_clock(traced):
    _, _, _, recs, _, trace_dir = traced
    (path,) = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                        recursive=True)
    events = [ev for plane in jax.profiler.ProfileData.from_file(path).planes
              if not plane.name.startswith("/device:")
              for line in plane.lines for ev in line.events
              if ev.name.startswith(tracing.PREFIX)]
    (clock,) = [ev for ev in events if ev.name == "tryage.clock"]
    offset_ns = clock.start_ns - dict(clock.stats)["mono_ns"]
    mirrors = {dict(ev.stats)["flush_id"]: ev for ev in events
               if ev.name == "tryage.flush"}
    flushes = [r for r in recs if r.name == "flush"]
    assert len(mirrors) == len(flushes)
    for r in flushes:
        ev = mirrors[r.attrs["flush_id"]]
        assert dict(ev.stats)["rows"] == r.attrs["rows"]
        assert abs(r.start * 1e9 + offset_ns - ev.start_ns) < 50e3


def test_results_identical_with_profiler_on_and_off(tiny_library, traced):
    _, _, on, _, _, _ = traced
    off = _serve(_engine(tiny_library, use_kernel=True),
                 _requests(21, seed=1))
    key = lambda r: r.uid                                      # noqa: E731
    for a, b in zip(sorted(on, key=key), sorted(off, key=key)):
        assert (a.uid, a.expert, a.loss, a.accuracy, a.flush_reason) == \
            (b.uid, b.expert, b.loss, b.accuracy, b.flush_reason)
        np.testing.assert_array_equal(a.predictions, b.predictions)
        np.testing.assert_array_equal(a.pred_losses, b.pred_losses)


def test_wait_histograms_render(traced):
    eng = traced[0]
    out = render(eng.stats)
    for name, n in (("tryage_queue_wait_seconds", 21),
                    ("tryage_lane_wait_seconds", 21)):
        assert f"# TYPE {name} histogram" in out
        assert f"{name}_count {n}" in out
        assert f'{name}_bucket{{le="+Inf"}} {n}' in out


def test_speculative_admissions_link_their_cascade(tiny_library, tmp_path):
    eng = _engine(tiny_library, speculate=True)
    reqs = _requests(16, seed=2, thresholds=(0.0, 0.4, 0.99))
    results, recs, _ = _traced(eng, reqs, tmp_path)
    assert sorted(r.uid for r in results) == list(range(16))
    admit_ids = {r.attrs["admit_id"] for r in recs if r.name == "admit"}
    cascades = [r for r in recs if r.name == "admit.cascade"]
    assert cascades
    assert {r.attrs["admit_id"] for r in cascades} <= admit_ids


def test_stalls_recorded_under_the_open_span(tmp_path):
    tracing.clear()
    with jax.profiler.trace(str(tmp_path)):
        with tracing.span("flush", rows=1) as sp:
            gc.collect()
            jax.jit(lambda x: x * 3 + 1)(jnp.arange(5.0))
    recs = tracing.records()
    tracing.clear()
    stalls = {r.name: r for r in recs if r.parent == sp.id}
    assert {"gc", "compile"} <= set(stalls)
    for r in stalls.values():
        assert sp.start <= r.start <= r.end <= recs[-1].end
