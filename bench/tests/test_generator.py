"""The traffic generator: phases, determinism, fixed work per seed."""

import numpy as np

from bench import generator

BURST = {"prompt_len": 16, "mask_rate": 0.15,
         "flag_cycle": [{}, {"size": 1.0}, {"size": 8.0}, {"recency": 2.0}],
         "priority_mix": [0.9, 0.08, 0.02], "min_confidence": 0.59,
         "phases": [{"seconds": 1.0, "rate_rps": 300.0},
                    {"seconds": 5.0, "rate_rps": 12.0}]}


def test_burst_phases_fall_where_the_file_says():
    due = generator.due_offsets(BURST, 30.0, np.random.default_rng(5))
    assert np.all(np.diff(due) >= 0) and due[0] == 0.0 and due[-1] < 30.0
    phase = due % 6.0
    for k in range(5):
        period = due[(due >= 6 * k) & (due < 6 * k + 6)]
        assert np.sum(period % 6.0 < 1.0) == 300
        assert np.sum(period % 6.0 >= 1.0) == 60
    assert len(due) == 5 * 360 and np.sum(phase < 1.0) == 1500


def test_steady_phase_spans_the_window():
    mix = dict(BURST, phases=[{"seconds": 0, "rate_rps": 50.0}])
    due = generator.due_offsets(mix, 10.0, np.random.default_rng(1))
    assert len(due) == 500 and due.max() < 10.0


def test_seed_fixes_traffic_and_only_reorders_the_work():
    a = generator.make_traffic(BURST, 12.0, 2**31 + 77)
    b = generator.make_traffic(BURST, 12.0, 2**31 + 77)
    c = generator.make_traffic(BURST, 12.0, 2**31 + 78)
    assert np.array_equal(a.due, b.due)
    assert all(np.array_equal(x.tokens, y.tokens)
               for x, y in zip(a.requests, b.requests))
    assert not np.array_equal(a.due, c.due)
    assert len(a.requests) == len(c.requests)
    for t in (a, c):
        prio = np.bincount([r.priority for r in t.requests], minlength=3)
        assert prio.tolist() == [648, 58, 14]
        assert {r.min_confidence for r in t.requests} == {0.59}
        toks = np.stack([r.tokens for r in t.requests])
        assert toks.min() >= 0 and toks.max() < generator.VOCAB
        assert all(r.mask[0] == 0 for r in t.requests)
        assert [r.lambdas for r in t.requests[:4]] == BURST["flag_cycle"]


def test_open_loop_emits_when_due_and_leaves_arrival_to_the_engine():
    mix = dict(BURST, phases=[{"seconds": 0, "rate_rps": 4.0}])
    t = generator.make_traffic(mix, 1.0, 3)
    clock = [100.0]
    closed = []
    emitted = []

    def sleep(s):
        clock[0] += s

    feed = generator.OpenLoop(t, 100.0, 1.0, lambda: closed.append(clock[0]),
                              clock=lambda: clock[0], sleep=sleep)
    out = []
    for r in feed:
        out.append(r)
        if r is not None:
            emitted.append(clock[0])
    reqs = [r for r in out if r is not None]
    assert [r.uid for r in reqs] == list(range(len(t.due)))
    assert all(r.arrival is None for r in reqs)
    assert feed.due.tolist() == [100.0 + d for d in t.due]
    assert all(0.0 <= e - d < 1e-3 for e, d in zip(emitted, feed.due))
    assert max(feed.lateness) < 1e-3
    assert closed and closed[0] >= 101.0 and out[-1] is None
