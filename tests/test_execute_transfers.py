"""The Execute stage's host-device transfers (``engine._run_expert``):
one batched put of a flush's inputs, the outputs' host copies started
at dispatch, one blocking wait per flush.  Outputs stay bit-identical
to a plain call-then-fetch of the same expert program, a warmed engine
compiles nothing while serving, and ``EngineStats.flush_round_trips``
counts one wait per flush, with and without a placement map."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.objective import recency_constraint, size_constraint
from repro.core.router import RouterConfig, init_router
from repro.data.batching import mlm_batch
from repro.launch.mesh import make_host_mesh
from repro.serving import Request, TryageEngine

RC = RouterConfig(n_models=3, vocab_size=64, num_layers=1, d_model=32,
                  num_heads=2, d_ff=64)
S = 32


def _engine(library, mesh, **kw):
    rp, _ = init_router(jax.random.PRNGKey(9), RC, uncertainty=True)
    cons = [size_constraint(library), recency_constraint(library)]
    if mesh:
        kw.update(mesh=make_host_mesh(1, 1), replicate_hot=1)
    return TryageEngine(library, rp, RC, cons, **kw)


def _requests(n, seed=0):
    rng = np.random.default_rng(seed)
    toks = rng.integers(4, 64, size=(n, S)).astype(np.int32)
    mb = mlm_batch(toks, rng, 0.2, 64)
    mix = [{}, {"size": 1.0}, {"size": 8.0}, {"recency": 2.0}]
    return [Request(uid=i, tokens=mb["tokens"][i], targets=mb["targets"][i],
                    mask=mb["mask"][i], lambdas=mix[i % len(mix)])
            for i in range(n)]


def _reference(fn, params, reqs, bucket):
    """The plain path: three host-to-device copies, the call, a wait,
    then each output fetched in turn."""
    toks, targets, mask = (np.zeros((bucket, S), np.int32)
                           for _ in range(3))
    for j, r in enumerate(reqs):
        toks[j], targets[j], mask[j] = r.tokens, r.targets, r.mask
    outs = fn(params, jnp.asarray(toks), jnp.asarray(targets),
              jnp.asarray(mask))
    jax.block_until_ready(outs)
    return tuple(np.asarray(o)[:len(reqs)] for o in outs)


@pytest.mark.parametrize("mesh", [False, True], ids=["single", "placement"])
@pytest.mark.parametrize("n, bucket, expert", [(1, 1, 0), (5, 8, 1),
                                               (128, 128, 2)])
def test_run_expert_bit_identical_to_plain_fetch(tiny_library, mesh, n,
                                                 bucket, expert):
    eng = _engine(tiny_library, mesh, lane_target=128)
    e = tiny_library[expert]
    reqs = _requests(n, seed=n)
    got = eng._run_expert(e, reqs)
    want = _reference(eng._expert_fns[e.name], e.params, reqs, bucket)
    assert len(got) == 3
    for g, w in zip(got, want):
        assert g.shape[0] == n and g.dtype == w.dtype
        np.testing.assert_array_equal(g, w)
    assert dict(eng.stats.bucket_hits) == {bucket: 1}
    assert eng.stats.padded_rows == bucket - n
    assert eng.stats.flush_round_trips == 1


def _warm(eng):
    """What the benchmark's set-up does before its window: every
    admission bucket of the decision step, and each expert's step at
    every lane bucket, on ``jnp.asarray`` int32 inputs (on a placement
    map, ``warm_mesh`` places them on each replica's device)."""
    n_c = len(eng.constraints)
    b = 1
    while b <= eng.max_batch:
        eng._decide(eng.router_params,
                    jnp.asarray(np.zeros((b, S), np.int32)),
                    jnp.asarray(np.zeros((b, n_c), np.float32)))
        b *= 2
    if eng.placement is not None:
        return eng.warm_mesh(S)
    for e in eng.library.experts:
        b = 1
        while b <= eng.lane_target:
            z = jnp.asarray(np.zeros((b, S), np.int32))
            jax.block_until_ready(eng._expert_fns[e.name](e.params, z, z, z))
            b *= 2


@pytest.mark.parametrize("mesh", [False, True], ids=["single", "placement"])
def test_warmed_serve_compiles_nothing_and_waits_once_per_flush(
        tiny_library, mesh):
    eng = _engine(tiny_library, mesh, use_kernel=True, max_batch=8,
                  lane_target=8)
    _warm(eng)
    sizes = {name: fn._cache_size() for name, fn in eng._expert_fns.items()}
    compiles = []

    def on_duration(event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        results = list(eng.serve(iter(_requests(37, seed=4))))
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert sorted(r.uid for r in results) == list(range(37))
    assert compiles == []
    assert {name: fn._cache_size()
            for name, fn in eng._expert_fns.items()} == sizes
    assert len(eng.stats.bucket_hits) > 1         # mixed buckets flushed
    n_flushes = sum(eng.stats.flushes.values())
    assert eng.stats.flush_round_trips == n_flushes > 0
    assert eng.stats.summary()["flush_round_trips"] == n_flushes
