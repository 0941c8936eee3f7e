#!/usr/bin/env python3
"""Bring-up smoke run of the Tryage serving path on a TPU.

    python chip_smoke.py [--seed 0]     # one chip
    python chip_smoke.py --chips 4      # the mesh path

One chip.  The 11-expert paper library (``paper_library_specs``: the
largest expert 6 x 256, vocabulary 512, 128-token requests) and the
default router (``RouterConfig(n_models=11)``, 4 x 128, with its
uncertainty head) take random weights from ``--seed``: routing, the
cascade and expert execution do not depend on trained weights, so
nothing is trained and nothing under ``experiments/`` is read but the
tracked tile table.  Requests go through ``launch.serve.serve_library``,
the code ``python -m repro.launch.serve`` runs, in three phases:

  serve    every stage on: the fused routing kernels compiled
           (``--use-kernel --fused-cascade --cascade T``), admission
           batches of up to 256 rows (two kernel tiles) and online
           adaptation (``--adapt-every``), so the Feedback stage's
           jitted router update runs too;
  single   single-shot routing through the ``router_score`` kernel,
           against a plain-jnp reference engine (``--use-kernel`` off);
  cascade  the one-launch ``router_cascade`` kernel against the same
           reference running the staged cascade.

The comparison phases run with adaptation off.  A kernel engine and its
reference must choose the same expert for every request whose
reference decision is clear: the gap between its best and second-best
combined score is at least ``TOL`` and, under the cascade, the first
pick's confidence is at least ``TOL_CONF`` from the threshold and the
escalation margin at least ``TOL``.
Near-ties are counted and printed, not hidden: XLA's default f32 matmul
precision on a TPU is not full f32.  The margin excuses choices only:
every predicted loss must agree with the reference's to ``PRED_ATOL``,
and where both chose the same expert the per-request NLL to
``NLL_RTOL``.  Every request gets
exactly one Result that did not fail, with a finite loss.

``--chips 4`` runs only the mesh path, on a (data=2, model=2) mesh:
placement planned from the meshless run's traffic with the hottest
experts replicated, ``warm_mesh``, the shard_map'd fused decision, and
a meshless engine in the same process to compare with, under the same
rules.  It prints which device ids hold each expert's replicas.

Earlier lines report each phase.  The last line is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Without a
TPU the script exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

PLATFORM = "tpu"       # the platform the run must find
THRESHOLD = 0.59       # cascade confidence floor: about the median
                       # confidence of a randomly initialised router
TOL = 2e-2             # near-tie margin of a combined score
TOL_CONF = 5e-3        # near-tie margin of a confidence vs its threshold
PRED_ATOL = 1e-3       # predicted-loss agreement with the reference
NLL_RTOL = 1e-3        # per-request NLL agreement of the same expert
ADAPT_EVERY = 64
REQUESTS = 512         # requests per phase

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
                 "/jax/compilation_cache/cache_misses": "misses"}
_compile_s = [0.0]
_cache = {"hits": 0, "misses": 0}


def _on_duration(event: str, duration: float, **_kw) -> None:
    if event == _BACKEND_COMPILE:
        _compile_s[0] += duration


def _on_event(event: str, **_kw) -> None:
    if event in _CACHE_EVENTS:
        _cache[_CACHE_EVENTS[event]] += 1


def fail(msg: str):
    raise SystemExit(f"chip_smoke: {msg}")


def report(name: str, fields: dict) -> None:
    print(f"{name}: {json.dumps(fields, default=str)}", flush=True)


def seeded_models(seed: int):
    """The paper library and the default router with random weights
    from ``seed``, and the synthetic corpus requests are sampled from."""
    import jax

    from repro.core.library import paper_library_specs, seeded_library
    from repro.core.router import RouterConfig, init_router
    from repro.data.corpus import DomainCorpus

    lib = seeded_library(paper_library_specs(), seed)
    rc = RouterConfig(n_models=len(lib))
    rp, _ = init_router(jax.random.fold_in(jax.random.PRNGKey(seed),
                                           len(lib)), rc, uncertainty=True)
    return lib, rp, rc, DomainCorpus(vocab_size=rc.vocab_size, seed=seed)


def timed_serve(models, flags, placement=None):
    """One ``serve_library`` run; returns it with its wall seconds and
    the XLA compile seconds spent inside it."""
    from repro.launch import serve
    lib, rp, rc, corpus = models
    c0, t0 = _compile_s[0], time.perf_counter()
    run = serve.serve_library(lib, rp, rc, corpus, serve.parse_args(flags),
                              placement=placement)
    return run, time.perf_counter() - t0, _compile_s[0] - c0


def check_answered(run, phase: str) -> None:
    """Exactly one Result per request, none failed, and a finite loss
    for every request with masked positions to score."""
    want = sorted(r.uid for r in run.requests)
    got = sorted(r.uid for r in run.results)
    if got != want:
        fail(f"{phase}: {len(got)} results for {len(want)} requests")
    scored = {r.uid for r in run.requests if r.mask.any()}
    bad = [r.uid for r in run.results
           if r.failed or (r.uid in scored and not (
               r.loss is not None and math.isfinite(r.loss)))]
    if bad:
        fail(f"{phase}: {len(bad)} requests failed or lost their loss "
             f"(uids {bad[:8]})")


def require_compiled(step, *args) -> dict:
    """The Pallas kernels run compiled: interpret mode is off, and the
    lowered decision step holds a TPU custom call.  Returns both facts
    for the phase's report line."""
    import jax

    from repro.kernels import default_interpret
    if default_interpret():
        fail("Pallas kernels would run in interpret mode")
    if "tpu_custom_call" not in jax.jit(step).lower(*args).as_text():
        fail("no tpu_custom_call in the lowered decision step")
    return {"default_interpret": False, "tpu_custom_call": True}


def reference_confidence(rp, rc, toks, chunk: int = 256) -> np.ndarray:
    """Per-expert confidence 1/(1+sigma) from the plain-jnp uncertainty
    head, in fixed-size chunks (one compile)."""
    import jax

    from repro.core.objective import confidence_scores
    from repro.core.router import predict_uncertainty
    f = jax.jit(lambda t: predict_uncertainty(rp, rc, {"tokens": t}))
    n = len(toks)
    pad = np.zeros((-n % chunk,) + toks.shape[1:], toks.dtype)
    padded = np.concatenate([toks, pad])
    sigma = np.concatenate([np.asarray(f(padded[i:i + chunk]))
                            for i in range(0, len(padded), chunk)])[:n]
    return confidence_scores(sigma)


def compare(run, ref, models, threshold: float) -> dict:
    """Choices and NLLs of ``run`` against the reference run ``ref``
    (same requests), with near-ties judged on the reference's numbers."""
    from repro.core.objective import constraint_matrix, escalation_order
    from repro.serving.requests import lambda_matrix

    lib, rp, rc, _ = models
    reqs = ref.requests
    M = rc.n_models
    got = {r.uid: r for r in run.results}
    exp = {r.uid: r for r in ref.results}
    cons = ref.engine.constraints
    pred = np.stack([exp[r.uid].pred_losses for r in reqs])
    comb = pred + lambda_matrix(reqs, [c.name for c in cons]) @ \
        constraint_matrix(cons, M)
    srt = np.sort(comb, axis=1)
    first = comb.argmin(axis=1)
    near = srt[:, 1] - srt[:, 0] < TOL
    if threshold > 0:
        conf = reference_confidence(rp, rc,
                                    np.stack([r.tokens for r in reqs]))
        c_first = conf[np.arange(len(reqs)), first]
        near |= np.abs(c_first - threshold) < TOL_CONF
        pos = np.empty(M, int)
        pos[escalation_order(lib)] = np.arange(M)
        for i in np.flatnonzero(c_first < threshold):
            above = np.sort(comb[i, pos > pos[first[i]]])
            if len(above) > 1 and above[1] - above[0] < TOL:
                near[i] = True
    agree = np.array([got[r.uid].expert == exp[r.uid].expert
                      for r in reqs])
    clear_disagree = int((~agree & ~near).sum())
    nll_rel = [abs(got[r.uid].loss - exp[r.uid].loss)
               / max(1.0, abs(exp[r.uid].loss))
               for r, a in zip(reqs, agree)
               if a and exp[r.uid].loss is not None]
    pred_diff = max(float(np.abs(got[r.uid].pred_losses
                                 - exp[r.uid].pred_losses).max())
                    for r in reqs)
    out = {"requests": len(reqs), "agree": int(agree.sum()),
           "near_ties": int(near.sum()),
           "near_tie_disagree": int((~agree & near).sum()),
           "clear_disagree": clear_disagree,
           "max_nll_rel_diff": max(nll_rel, default=0.0),
           "max_pred_loss_diff": pred_diff, "tol": TOL,
           "tol_conf": TOL_CONF, "pred_atol": PRED_ATOL}
    if pred_diff > PRED_ATOL:
        fail(f"predicted losses off the reference by more than "
             f"{PRED_ATOL}: {out}")
    if clear_disagree:
        fail(f"{clear_disagree} requests chose differently from the "
             f"reference outside the near-tie margin {TOL}: {out}")
    if out["max_nll_rel_diff"] > NLL_RTOL:
        fail(f"per-request NLL off the reference by more than "
             f"{NLL_RTOL}: {out}")
    return out


def wide_batches(stats) -> int:
    """Fused decision launches whose padded batch spans several kernel
    tiles."""
    return sum(t["launches"] for t in stats.router_tiles.values()
               if t["grid"] > 1)


def _phase_fields(run, wall, comp) -> dict:
    st = run.engine.stats
    return {"requests": len(run.requests), "results": len(run.results),
            "router_tiles": {b: {"grid": t["grid"],
                                 "launches": t["launches"]}
                             for b, t in st.router_tiles.items()},
            "batches_over_one_tile": wide_batches(st),
            "escalations": st.escalations,
            "compile_s": comp, "wall_s": wall, "serve_s": wall - comp}


def smoke_one_chip(models, *, requests: int, seq: int,
                   max_batch: int) -> None:
    import jax.numpy as jnp
    base = ["--requests", str(requests), "--seq", str(seq),
            "--max-batch", str(max_batch)]
    cascade = ["--cascade", str(THRESHOLD), "--cascade-depth", "1"]

    run, wall, comp = timed_serve(
        models, base + ["--use-kernel", "--fused-cascade", *cascade,
                        "--adapt-every", str(ADAPT_EVERY)])
    check_answered(run, "serve")
    eng, st = run.engine, run.engine.stats
    toks = jnp.zeros((max_batch, seq), jnp.int32)
    lam = jnp.zeros((max_batch, len(eng.constraints)), jnp.float32)
    fields = _phase_fields(run, wall, comp)
    fields.update(**require_compiled(eng._decide_cascade, eng.router_params,
                                     toks, lam),
                  adapt_updates=st.adapt_updates,
                  router_version=run.engine.router_version)
    report("phase serve", fields)
    if fields["batches_over_one_tile"] == 0:
        fail("no admission batch spanned more than one kernel tile")
    if st.adapt_updates == 0:
        fail("the Feedback stage applied no router update")

    for phase, extra in (("single", []), ("cascade", cascade)):
        kflags = ["--use-kernel"] + (["--fused-cascade"] if extra else [])
        ref, r_wall, r_comp = timed_serve(models, base + extra)
        run, wall, comp = timed_serve(models, base + kflags + extra)
        check_answered(ref, f"{phase} reference")
        check_answered(run, phase)
        eng = run.engine
        step = eng._decide_cascade if extra else eng._decide
        fields = _phase_fields(run, wall, comp)
        fields.update(**require_compiled(step, eng.router_params, toks, lam),
                      **compare(run, ref, models,
                                THRESHOLD if extra else 0.0),
                      reference_compile_s=r_comp, reference_wall_s=r_wall)
        report(f"phase {phase}", fields)


def smoke_mesh(models, *, requests: int, seq: int, max_batch: int) -> None:
    from repro.serving.placement import plan_placement
    lib = models[0]
    base = ["--requests", str(requests), "--seq", str(seq),
            "--max-batch", str(max_batch), "--use-kernel",
            "--cascade", str(THRESHOLD), "--cascade-depth", "1"]
    ref, r_wall, r_comp = timed_serve(models, base)
    check_answered(ref, "meshless")
    names = [e.name for e in lib]
    counts = np.bincount([names.index(r.expert) for r in ref.results],
                         minlength=len(lib))
    placement = plan_placement([e.n_params for e in lib], 2,
                               replicate_hot=2,
                               traffic=counts / counts.sum())
    run, wall, comp = timed_serve(
        models, base + ["--mesh", "2,2", "--replicate-hot", "2"],
        placement=placement)
    check_answered(run, "mesh")
    ms = run.engine.mesh_summary()
    held = sorted({d for ids in ms["replica_devices"].values()
                   for d in ids})
    report("mesh placement", {"replica_devices": ms["replica_devices"],
                              "devices_holding_experts": held,
                              "stream_flushes": ms["streams"]["flushes"]})
    if len(held) < 2:
        fail(f"every expert replica sits on device(s) {held}")
    if sum(1 for f in ms["streams"]["flushes"] if f > 0) < 2:
        fail("flushes all landed in one device stream")
    fields = _phase_fields(run, wall, comp)
    fields.update(compare(run, ref, models, THRESHOLD),
                  reference_compile_s=r_comp, reference_wall_s=r_wall)
    report("phase mesh", fields)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != PLATFORM:
        print(f"chip_smoke: needs a {PLATFORM.upper()}, found platform "
              f"{dev.platform!r}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} devices, "
             f"found {len(devices)}")
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)
    report("device", {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(devices), "compile_cache": cache_dir})

    models = seeded_models(args.seed)
    sizes = dict(requests=REQUESTS, seq=128, max_batch=256)
    if args.chips == 4:
        smoke_mesh(models, **sizes)
    else:
        smoke_one_chip(models, **sizes)

    mem = {d.id: (d.memory_stats() or {}).get("peak_bytes_in_use")
           for d in devices[:args.chips]}
    report("memory", {"peak_bytes_in_use": mem})
    report("compile cache", {"dir": cache_dir, **_cache})
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
