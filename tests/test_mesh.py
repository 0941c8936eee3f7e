"""Mesh-sharded serving: placement planning, per-device stream
bookkeeping, mesh-construction validation, and the multi-device parity
suite.

The load-bearing contract is feature-off parity: a ``(1, 1)`` mesh
engine must be **bit-for-bit** the meshless engine — identical Results
and identical ``EngineStats`` — including cascade escalations and
health-fallback reroute traffic, under both the host and the fused
Pallas scoring paths.  Mesh telemetry (placement map, stream clocks)
lives outside ``EngineStats`` precisely so this holds by construction.

Multi-device tests need the CI mesh leg's 8 virtual CPU devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``, set before jax
imports) and skip elsewhere; they pin the sharded engine's routing
choices to the single-device engine's exactly and its measured
per-request NLLs to within float tolerance.
"""

import dataclasses

import jax
import numpy as np
import pytest

from repro.core.router import RouterConfig, init_router
from repro.data.batching import mlm_batch
from repro.launch.mesh import make_host_mesh
from repro.serving import ExpertHealth, ExpertScheduler, Request, TryageEngine
from repro.serving.placement import PlacementMap, StreamClock, plan_placement

RC = RouterConfig(n_models=3, vocab_size=64, num_layers=1, d_model=32,
                  num_heads=2, d_ff=64)

multidevice = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


class Clock:
    def __init__(self, t=1.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


@pytest.fixture(scope="module")
def router_params():
    rp, _ = init_router(jax.random.PRNGKey(9), RC)
    return rp


def _requests(n, seed=0, min_confidence=0.0, n_unique=None):
    n_unique = n if n_unique is None else n_unique
    rng = np.random.default_rng(seed)
    toks = rng.integers(4, 64, size=(n_unique, 32)).astype(np.int32)
    mb = mlm_batch(toks, rng, 0.2, 64)
    mix = [{}, {"size": 1.0}, {"size": 8.0}, {"recency": 2.0}]
    return [Request(uid=i, tokens=mb["tokens"][i % n_unique],
                    targets=mb["targets"][i % n_unique],
                    mask=mb["mask"][i % n_unique],
                    lambdas=mix[i % len(mix)],
                    min_confidence=min_confidence)
            for i in range(n)]


def _engine(library, params, clock, **kw):
    from repro.core.objective import recency_constraint, size_constraint
    cons = [size_constraint(library), recency_constraint(library)]
    kw.setdefault("max_batch", 32)
    return TryageEngine(library, params, RC, cons, now_fn=clock, **kw)


def _result_key(r):
    d = dataclasses.asdict(r)
    d["pred_losses"] = d["pred_losses"].tobytes()
    d["predictions"] = d["predictions"].tobytes()
    return d


def _hot_expert(library, params, reqs):
    """Post-cascade routing argmax-by-traffic, computed on a throwaway
    scout engine so the engines under test keep pristine stats."""
    scout = _engine(library, params, Clock())
    pred, choice = scout._score_batch(reqs)
    choice, _, _ = scout._cascade(reqs, pred, choice)
    return int(np.bincount(np.asarray(choice), minlength=3).argmax())


# ---------------------------------------------------- placement planning


def test_plan_placement_is_lpt_balanced_and_deterministic():
    sizes = [8.0, 7.0, 3.0, 2.0, 1.0, 1.0]
    pm = plan_placement(sizes, n_slices=2)
    # LPT walk: 8->s0, 7->s1, 3->s1, 2->s0, 1->s0 (tie, low index), 1->s1
    assert [pm.home(i) for i in range(6)] == [0, 1, 1, 0, 0, 1]
    per_slice = [sum(sizes[i] for i in range(6) if pm.home(i) == k)
                 for k in range(2)]
    assert per_slice == [11.0, 11.0]
    assert pm == plan_placement(sizes, n_slices=2)       # deterministic
    assert not any(pm.replicated(i) for i in range(6))


def test_plan_placement_traffic_weights_override_size():
    """A small expert carrying all the traffic becomes the heaviest
    load and claims its own slice."""
    sizes = [100.0, 1.0]
    uniform = plan_placement(sizes, n_slices=2)
    skewed = plan_placement(sizes, n_slices=2, traffic=[0.001, 0.999])
    assert uniform.home(0) == 0                          # size order
    assert skewed.home(1) == 0                           # load order
    assert skewed.home(0) == 1


def test_plan_placement_replicates_hot_experts_home_first():
    pm = plan_placement([5.0, 4.0, 1.0], n_slices=3, replicate_hot=2)
    for i in (0, 1):
        assert pm.replicated(i)
        ss = pm.slices_for(i)
        assert ss[0] == pm.home(i) and sorted(ss) == [0, 1, 2]
    assert pm.slices_for(2) == (pm.home(2),)


def test_plan_placement_single_slice_never_replicates():
    pm = plan_placement([5.0, 4.0, 1.0], n_slices=1, replicate_hot=2)
    assert pm.slices == ((0,), (0,), (0,))


def test_plan_placement_rejects_bad_inputs():
    with pytest.raises(AssertionError):
        plan_placement([1.0, 0.0], n_slices=2)           # non-positive size
    with pytest.raises(AssertionError):
        plan_placement([1.0], n_slices=2, traffic=[0.5, 0.5])
    with pytest.raises(AssertionError):
        PlacementMap(2, ((0,), (2,)))                    # slice out of range
    with pytest.raises(AssertionError):
        PlacementMap(2, ((0, 0),))                       # duplicate replica


def test_placement_summary_names_slices_and_replicas():
    pm = plan_placement([5.0, 4.0, 1.0], n_slices=2, replicate_hot=1)
    s = pm.summary(["a", "b", "c"])
    assert s["n_slices"] == 2
    assert s["replicated"] == ["a"]
    assert sorted(x for members in s["per_slice"].values()
                  for x in members) == ["a", "a", "b", "c"]


# ------------------------------------------------------------ streams


def test_stream_clock_accounting_and_dispatch():
    sc = StreamClock(3)
    sc.record(0, 2.0, tokens=100)
    sc.record(2, 0.5, tokens=10)
    assert sc.least_busy([0, 2]) == 2
    assert sc.least_busy([1, 2]) == 1                    # tie -> low index is
    sc.record(1, 0.5, tokens=10)                         # moot: 1 is idle
    assert sc.makespan_s == 2.0
    assert sc.total_busy_s == pytest.approx(3.0)
    sc.record_failure(2)
    s = sc.summary()
    assert s["flushes"] == [1, 1, 1] and s["failures"] == [0, 0, 1]
    assert s["tokens"] == [100, 10, 10]
    sc.reset()
    assert sc.makespan_s == 0.0 and sc.summary()["flushes"] == [0, 0, 0]


def test_scheduler_assigns_lane_slots_from_placement():
    pm = plan_placement([3.0, 2.0, 1.0], n_slices=2)
    sched = ExpertScheduler(n_experts=3, target=4, max_wait_s=1.0)
    assert all(lane.slot is None for lane in sched.lanes.values())
    sched.assign_slots(pm)
    for i in range(3):
        assert sched.lanes[i].slot == pm.home(i)
        assert sched.esc_lanes[i].slot == pm.home(i)


# ----------------------------------------------------- mesh validation


def test_host_mesh_error_names_the_xla_flag():
    need = 64 * 64
    if jax.device_count() >= need:                       # pragma: no cover
        pytest.skip("impossibly large host")
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        make_host_mesh(64, 64)
    with pytest.raises(ValueError, match=str(need)):
        make_host_mesh(64, 64)                           # says how many


def test_host_mesh_rejects_nonpositive_axes():
    with pytest.raises(ValueError):
        make_host_mesh(0, 1)


def test_engine_rejects_mesh_without_serving_axes(tiny_library,
                                                  router_params):
    mesh = jax.make_mesh((1,), ("x",))
    with pytest.raises(ValueError, match="data"):
        _engine(tiny_library, router_params, Clock(), mesh=mesh)


def test_engine_rejects_mismatched_placement(tiny_library, router_params):
    mesh = make_host_mesh(1, 1)
    with pytest.raises(ValueError):
        _engine(tiny_library, router_params, Clock(), mesh=mesh,
                placement=plan_placement([1.0, 1.0, 1.0], n_slices=2))
    with pytest.raises(ValueError):
        _engine(tiny_library, router_params, Clock(), mesh=mesh,
                placement=plan_placement([1.0, 1.0], n_slices=1))


# ------------------------------------------------ single-device parity


@pytest.mark.parametrize("use_kernel", [False, True])
def test_1x1_mesh_engine_is_bit_for_bit_meshless(tiny_library,
                                                 router_params,
                                                 use_kernel):
    """The acceptance gate: a (1, 1)-mesh engine serving the mixed-flag
    workload — with cascade escalations AND injected flush failures
    driving health-fallback reroutes — produces identical Results and
    identical EngineStats to the meshless engine."""
    reqs = _requests(96, seed=7, min_confidence=0.99, n_unique=64)
    hot = _hot_expert(tiny_library, router_params, reqs)
    outs, stats, engines = [], [], []
    for mesh in (None, make_host_mesh(1, 1)):
        clock = Clock()
        eng = _engine(tiny_library, router_params, clock, lane_target=8,
                      max_wait_s=1e9, use_kernel=use_kernel,
                      health=ExpertHealth(3, now_fn=clock),
                      mesh=mesh, replicate_hot=1)

        def stream():
            for i, r in enumerate(reqs):
                if i == 0:
                    # two failed flushes -> reroute + health penalty
                    eng.scheduler.inject_failures(hot, count=2)
                clock.advance(0.001)
                yield r

        out = list(eng.serve(stream()))
        assert len(out) == 96
        outs.append(sorted(out, key=lambda r: r.uid))
        stats.append(eng.stats.summary())
        engines.append(eng)
    for a, b in zip(*outs):
        assert _result_key(a) == _result_key(b)
    assert stats[0] == stats[1]
    # the traffic actually exercised the interesting paths
    assert stats[0]["cascade"]["escalations"] > 0
    assert stats[0]["fallback"]["reroutes"] > 0
    # mesh telemetry exists on the mesh engine only, outside the stats
    assert engines[0].mesh_summary() is None
    ms = engines[1].mesh_summary()
    assert ms["mesh"] == {"data": 1, "model": 1}
    assert ms["streams"]["streams"] == 1
    assert ms["streams"]["flushes"][0] > 0


def test_warm_mesh_compiles_every_variant(tiny_library, router_params):
    """warm_mesh covers the full (expert, replica device, bucket size)
    grid — dispatch can never hit a cold variant — and is a no-op on a
    meshless engine.  Warming charges no stream time."""
    assert _engine(tiny_library, router_params, Clock()).warm_mesh(32) == 0
    eng = _engine(tiny_library, router_params, Clock(), lane_target=8,
                  mesh=make_host_mesh(1, 1), replicate_hot=1)
    # 3 experts x 1 device x buckets {1, 2, 4, 8}
    assert eng.warm_mesh(32) == 12
    assert eng.streams.summary()["flushes"] == [0]
    assert eng.streams.makespan_s == 0.0


# ------------------------------------------------- multi-device parity


@multidevice
@pytest.mark.parametrize("use_kernel", [False, True])
def test_2x4_mesh_matches_single_device_choices_and_nll(tiny_library,
                                                        router_params,
                                                        use_kernel):
    """On 8 virtual CPU devices a (2, 4) mesh — data-parallel routing,
    experts spread over 4 slices with the hottest replicated — must
    agree with the meshless engine on every routing choice exactly and
    on every measured per-request NLL to float tolerance."""
    # mixed cascade thresholds: every 4th request escalates (constant
    # uncertainty prior -> conf 0.5 < 0.99), the rest keep the router's
    # first pick so traffic spreads over the library.  The escalating
    # rows carry the heavy size penalty, so their first pick sits below
    # the top rung and has somewhere to escalate to.
    reqs = [dataclasses.replace(r, min_confidence=0.99 if i % 4 == 2
                                else 0.0)
            for i, r in enumerate(_requests(128, seed=11, n_unique=96))]
    outs, engines = [], []
    for mesh in (None, make_host_mesh(2, 4)):
        clock = Clock()
        eng = _engine(tiny_library, router_params, clock, lane_target=8,
                      max_wait_s=1e9, use_kernel=use_kernel,
                      mesh=mesh, replicate_hot=1)
        out = list(eng.serve(iter(reqs)))
        assert len(out) == 128
        outs.append(sorted(out, key=lambda r: r.uid))
        engines.append(eng)
    for a, b in zip(*outs):
        assert a.expert == b.expert
        assert a.cascade_depth == b.cascade_depth
        if a.loss is not None or b.loss is not None:
            np.testing.assert_allclose(b.loss, a.loss, rtol=1e-5)
    # flush accounting: every flush landed in some device stream, and
    # the placement actually spread work over multiple streams
    eng = engines[1]
    st = eng.mesh_summary()["streams"]
    assert sum(st["flushes"]) == sum(eng.stats.flushes.values())
    # distinct home slices -> flushes land in multiple device streams
    # (busy_s stays 0.0 under the fake clock, so count flushes instead)
    assert sum(1 for f in st["flushes"] if f > 0) > 1
    assert eng.placement.n_slices == 4
    assert len(eng.stats.per_expert) > 1
    assert eng.stats.escalations > 0


@multidevice
def test_mesh_fallback_parity(tiny_library, router_params):
    """Failure-injection traffic (reroutes via health fallback) routes
    identically on the (2, 4) mesh, and failed flushes are charged to
    the failing expert's device streams."""
    reqs = _requests(64, seed=5)
    hot = _hot_expert(tiny_library, router_params, reqs)
    hot_name = tiny_library.experts[hot].name
    outs, engines = [], []
    for mesh in (None, make_host_mesh(2, 4)):
        clock = Clock()
        eng = _engine(tiny_library, router_params, clock, lane_target=8,
                      max_wait_s=1e9,
                      health=ExpertHealth(3, now_fn=clock),
                      mesh=mesh, replicate_hot=1)

        def stream():
            for i, r in enumerate(reqs):
                if i == 0:
                    eng.scheduler.inject_failures(hot)   # fail every flush
                yield r

        out = list(eng.serve(stream()))
        assert len(out) == 64
        assert all(not r.failed for r in out)
        assert all(r.expert != hot_name for r in out)
        outs.append(sorted(out, key=lambda r: r.uid))
        engines.append(eng)
    for a, b in zip(*outs):
        assert a.expert == b.expert
        assert a.fallback_depth == b.fallback_depth
    st = engines[1].mesh_summary()["streams"]
    assert sum(st["failures"]) >= 1                      # charged somewhere
