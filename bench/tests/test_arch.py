"""Expert architectures found by name (``bench/arch``).

The encoder's weights, readings, FLOPs and parameter counts are the
ones the harness had before they moved behind ``bench/arch/encoder.py``:
the digests and numbers below were recorded from that code on the CPU
backend under its default flags.  A second architecture, kept in the
test data alone (``data/bench/arch/swiglu_gqa.py``), is served beside
encoders, checked by a whole run and caught by its control.
"""

import hashlib
import os

import jax
import numpy as np
import pytest

from bench import arch, build, flops, harness, run_cell

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
BENCH = os.path.dirname(os.path.dirname(DATA))
PEAKS = flops.PEAKS["TPU v5 lite"]
ENCODER = arch.load("encoder")

# sha256 of every leaf (dtype, shape, bytes) in tree order, and of the
# readings (gap, nll, first), from the code before the move.
TINY = {
    "seed": 3,
    "weights": [
        "2d692045e818ae6c8eb953f12d717230fb7de957bfb77326c6f2e572c70b323f",
        "86187c439c707fcc645d0797f2590449f4aeef0ee11cbb456257c60f36cf7025",
        "d97d9c809464b12ddad344edc509cd0e076b1999af2ee5d83ac8bfbf6952b04f"],
    "router":
        "1070e3010e2eee1f114016fe57bf5ed6dcfdee0e30421ff4f47e23dea3b93f6e",
    "flops": [909312, 3661824, 4673536],
    "params": [27584, 110976, 140800],
    "readings": {
        "f32": [
            "b852e4eb549969a41bbd591724bf407304d6bd9e391382502801197b301806dd",
            "4d5e3126354ea2d7890f171139fbd802b3183b823f82f20bc1ab2c517771d270",
            "87da6234301c4fef0e037a4813f20644f5c1db89e4e74e632955df6d36673f58"],
        "fp8": [
            "6a110df8f7cf0a2cd95c9335b9d3712a8a21af829e1a0b6e6175d83b887a6d43",
            "427422ec6467a3cd3c351c5fe07d932c95d7d2b632ed87164b6f7e24bad91dcd",
            "51e22bfe449ed835186b6a5b493af6d36f99fc3a91205099083eb7a67475e2f0"]},
}
BERT_BASE = {
    "seed": 1,
    "weights":
        "247072ff40a632898198c6717f8f4baaf6d16bab1c63a27a6eb095e7c1145e76",
    "router":
        "c4a232e1ab684e036dfdc1730df8d22fcd4e20da28f68bdf85d0c257f0e349f8",
    "flops": 28348121088,
    "params": 108413952,
    "readings": {
        "f32":
            "9ecaadb0b0d3d8d32749567a0dd9454c5236be6a4511d84da9edfd09fee25364",
        "fp8":
            "25bc6474d72bd47b620a37ceeacda039e82da9dd8f3dcbf26d45d3b2050a69b0"},
}


def digest(*arrays):
    h = hashlib.sha256()
    for a in arrays:
        a = np.asarray(a)
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def tree_digest(tree):
    return digest(*jax.tree.leaves(tree))


def inputs(rows, seq, seed):
    """(tokens, served, targets, mask) over the corpus's 512 ids."""
    rng = np.random.default_rng(seed)
    toks = rng.integers(4, 512, size=(rows, seq)).astype(np.int32)
    served = rng.integers(0, 512, size=(rows, seq)).astype(np.int32)
    mask = (rng.random((rows, seq)) < 0.15).astype(np.int32)
    return toks, served, toks, mask


def test_encoder_is_bit_identical_on_tiny():
    cfg = harness.load_json(os.path.join(DATA, "bench", "configs",
                                         "tiny.json"))
    archs = arch.of(cfg, DATA)
    assert archs == [ENCODER] * 3
    params, router = build.make_weights(cfg, TINY["seed"], archs)
    assert [tree_digest(p) for p in params] == TINY["weights"]
    assert tree_digest(router) == TINY["router"]
    es = cfg["experts"]
    assert [ENCODER.request_flops(e, 16) for e in es] == TINY["flops"]
    assert [ENCODER.param_count(e) for e in es] == TINY["params"]
    t, s, g, m = inputs(4, 16, 0)
    for prec, want in TINY["readings"].items():
        assert [digest(*ENCODER.readings(p, e, t, s, g, m, prec))
                for p, e in zip(params, es)] == want, prec


def test_encoder_is_bit_identical_on_paper11_bert_base():
    """One expert of ``paper11`` at its published widths, drawn as the
    first expert of a library."""
    cfg = harness.load_json(os.path.join(BENCH, "configs", "paper11.json"))
    e = next(e for e in cfg["experts"] if e["name"] == "bert-base")
    cfg = dict(cfg, experts=[e])
    params, router = build.make_weights(cfg, BERT_BASE["seed"],
                                          arch.of(cfg))
    assert tree_digest(params[0]) == BERT_BASE["weights"]
    assert tree_digest(router) == BERT_BASE["router"]
    assert ENCODER.request_flops(e, 128) == BERT_BASE["flops"]
    assert ENCODER.param_count(e) == BERT_BASE["params"]
    t, s, g, m = inputs(2, 128, 1)
    for prec, want in BERT_BASE["readings"].items():
        assert digest(*ENCODER.readings(params[0], e, t, s, g, m,
                                        prec)) == want, prec


def test_arch_is_found_under_the_runs_root_first():
    assert arch.load("swiglu_gqa", DATA).__file__ == os.path.join(
        DATA, "bench", "arch", "swiglu_gqa.py")
    assert arch.load("encoder", DATA) is ENCODER
    with pytest.raises(SystemExit):
        arch.load("swiglu_gqa")


def mixed_cfg():
    return harness.load_json(os.path.join(DATA, "bench", "configs",
                                          "tiny-mixed.json"))


def test_other_arch_matches_program_in_float32():
    """At float32 the program's expert step and the test architecture's
    reference agree to rounding, and its parameter count is the
    program's."""
    from repro.models.model import count_params
    from repro.serving.engine import TryageEngine
    cfg = dict(mixed_cfg(), dtype="float32")
    archs = arch.of(cfg, DATA)
    params, _ = build.make_weights(cfg, 5, archs)
    for a, e, p in zip(archs, cfg["experts"], params):
        assert a.param_count(e) == count_params(p), e["name"]
    a, e, p = archs[1], cfg["experts"][1], params[1]
    assert e.get("arch") == "swiglu_gqa"
    rng = np.random.default_rng(0)
    toks = rng.integers(4, 512, size=(4, 16)).astype(np.int32)
    mask = (rng.random((4, 16)) < 0.3).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        preds, loss, _ = TryageEngine._expert_forward(
            p, toks, toks, mask, cfg=a.model_config(e["name"], e,
                                                    "float32"))
    gap, nll, first = a.readings(p, e, toks, np.asarray(preds), toks, mask)
    assert np.array_equal(np.asarray(preds), np.asarray(first))
    assert np.max(np.asarray(gap)) == 0.0
    np.testing.assert_allclose(np.asarray(loss), np.asarray(nll),
                               rtol=1e-5)


def test_other_arch_run_is_correct():
    out = run_cell.main(["--workload", "tiny-mixed-steady", "--seed",
                         str(2**31 + 31), "--seconds", "2", "--trace", "0"],
                        root=DATA, require_tpu=False, peaks=PEAKS)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] == 400


def test_other_arch_control_fails():
    """On three seeds, read on the sampled requests that the test
    architecture served and on no others: the program passes the
    expert limits, and that architecture's reference in float8 put in
    its place fails one of them.  The router is read in float32, so its
    own control decides nothing here."""
    cell = harness.Cell.load(DATA, "tiny-mixed-steady")
    system = harness.System(cell, 41)
    system.warm(cell.cascade)
    keys = ("token_logit_gap", "nll_rel_gap")
    limit = {k: cell.limits[k]["limit"] for k in keys}
    for seed in (41, 42, 43):
        if seed != 41:
            system.reseed(seed)
        _, traffic, results, _, _ = harness.serve_window(
            system, cell, 2, seed, None, 0.0, PEAKS)
        ex = harness.sample_served(results, harness.EXPERT_SAMPLE,
                                   np.random.default_rng([seed, 7]))
        mine = [u for u in ex if results[u].expert == "g"]
        assert mine

        def read(prec):
            tok, nll = harness.expert_readings(
                system.cfg, system.archs, system.expert_params, traffic,
                results, mine, prec)
            return dict(zip(keys, (np.max(tok), np.nanmax(nll))))
        ok, ctl = read("f32"), read("fp8")
        assert all(ok[k] <= limit[k] for k in keys), (seed, ok)
        assert any(ctl[k] > limit[k] for k in keys), (seed, ctl)
