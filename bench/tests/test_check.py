"""The output check fails where it must, on a CPU-sized cell.

``test_control_fails`` puts the plain reference, one precision below
what the configuration states, in the program's place and reads the
same numbers the check compares.  ``test_fault`` drives the rest of a
whole run (everything but the look for a chip) with the timed path
broken underneath, and sees ``correct`` come out false.  The sound run
comes out true under the same limits.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops, harness, run_cell

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PEAKS = flops.PEAKS["TPU v5 lite"]


def run(cell, seed, fault=None, trace=0):
    return run_cell.main(["--workload", cell, "--seed", str(seed),
                          "--seconds", "2", "--trace", str(trace)],
                         root=DATA, require_tpu=False, peaks=PEAKS,
                         fault=fault)


def _wrap_experts(system, change):
    """Replace every expert step by ``change`` applied around it."""
    eng = system.eng
    for name, fn in list(eng._expert_fns.items()):
        eng._expert_fns[name] = jax.jit(change(fn))


def token_altered(system):
    def change(fn):
        def step(p, toks, targets, mask):
            preds, loss, acc = fn(p, toks, targets, mask)
            return preds.at[0, 5].add(1), loss, acc
        return step
    _wrap_experts(system, change)


def half_batch_left_out(system):
    """The second half of every flush is computed from the first half's
    rows: those requests get another prompt's answer."""
    def change(fn):
        def step(p, toks, targets, mask):
            half = (toks.shape[0] + 1) // 2
            take = jnp.arange(toks.shape[0]) % half
            return fn(p, toks[take], targets[take], mask[take])
        return step
    _wrap_experts(system, change)


def verdict_altered(system):
    """The decision step sends row 0 of each batch to the next expert."""
    eng = system.eng
    m = eng.rc.n_models
    if eng.fused_cascade:
        fn = eng._decide_cascade

        def decide(p, toks, lam):
            pred, sigma, choice, esc = fn(p, toks, lam)
            return pred, sigma, choice.at[0].set((choice[0] + 1) % m), esc
        eng._decide_cascade = jax.jit(decide)
    fn2 = eng._decide

    def decide2(p, toks, lam):
        pred, choice = fn2(p, toks, lam)
        return pred, choice.at[0].set((choice[0] + 1) % m)
    eng._decide = jax.jit(decide2)


def request_dropped(system):
    flush = system.eng.pipeline.flush
    dropped = []

    def drop(expert_idx, entries, reason):
        out = flush(expert_idx, entries, reason)
        if not dropped and out:
            dropped.append(out.pop(0).uid)
        return out
    system.eng.pipeline.flush = drop


def test_sound_run_is_correct():
    out = run("tiny-steady", 2**31 + 11)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] == 400


@pytest.mark.parametrize("cell", ["tiny-steady", "tiny-cascade"])
@pytest.mark.parametrize("fault", [token_altered, half_batch_left_out,
                                   verdict_altered, request_dropped],
                         ids=lambda f: f.__name__)
def test_fault(cell, fault):
    out = run(cell, 2**31 + 12, fault=fault)
    assert not out["correct"], out["checks"]


def test_control_fails():
    """On three seeds, the control (experts and router in float8)
    fails one of the cell's numbers against the limits the
    program's own runs pass."""
    cell = harness.Cell.load(DATA, "tiny-cascade")
    system = harness.System(cell.cfg, 21)
    system.warm(cell.cascade)
    for seed in (21, 22, 23):
        if seed != 21:
            system.reseed(seed)
        _, traffic, results, dup, _ = harness.serve_window(
            system, cell, 2, seed, None, 0.0, PEAKS)
        ok = harness.check(len(traffic.requests), results, dup,
                           harness.readings(system, traffic, results, seed),
                           cell.limits)
        ctl = harness.check(len(traffic.requests), results, dup,
                            harness.readings(system, traffic, results, seed,
                                             expert_prec="fp8",
                                             router_prec="fp8"),
                            cell.limits)
        assert all(c["value"] <= c["limit"] for c in ok.values()), ok
        assert any(c["value"] > c["limit"] for c in ctl.values()), ctl


def test_reference_matches_program_in_float32():
    """At float32 the program's expert step and the reference agree to
    rounding: the reference is the same model."""
    from bench import build, reference, weights
    cfg = harness.load_json(os.path.join(DATA, "bench", "configs",
                                         "tiny.json"))
    cfg = dict(cfg, dtype="float32")
    params, _ = weights.make_weights(cfg, 5)
    e = cfg["experts"][2]
    mc = build.model_config(e["name"], e, "float32")
    from repro.serving.engine import TryageEngine
    rng = np.random.default_rng(0)
    toks = rng.integers(4, 512, size=(4, 16)).astype(np.int32)
    mask = (rng.random((4, 16)) < 0.3).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        preds, loss, _ = TryageEngine._expert_forward(
            params[2], toks, toks, mask, cfg=mc)
    gap, nll, first = reference.expert_readings(
        params[2], toks, np.asarray(preds), toks, mask)
    assert np.array_equal(np.asarray(preds), np.asarray(first))
    assert np.max(np.asarray(gap)) == 0.0
    np.testing.assert_allclose(np.asarray(loss), np.asarray(nll),
                               rtol=1e-5)
