"""One run of one cell: build the system from the configuration file,
warm its shapes, serve the traffic mix open-loop for the window, check
what the timed path produced against the plain references, and reduce
counters, host spans and (traced runs) the device trace to metrics.

Everything that belongs to one configuration, traffic mix or metric is
found by name: ``bench/configs/<config>.json``,
``bench/traffic/<traffic>.json``, ``bench/limits/<config>.json``,
``bench/arch/<arch>.py`` for each expert's architecture (see
``bench/arch``) and ``bench/metrics/<metric>.py`` (a module with
``read(run)``, returning the metric's value or ``None`` where it finds
nothing to read).
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import sys
import time

import numpy as np

from bench import arch, flops, generator, reference, router

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REF_ROWS = 8              # expert reference block
ROUTER_ROWS = 256         # router reference block
EXPERT_SAMPLE = 48        # served requests whose expert outputs are compared
ROUTER_SAMPLE = 4096      # served requests whose verdicts are compared


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """A workload of ``BENCHMARK.json`` with its files read."""
    name: str
    chips: int
    bench: dict
    cfg: dict
    mix: dict
    limits: dict
    archs: list                # each expert's architecture module

    @classmethod
    def load(cls, root: str, name: str) -> "Cell":
        bench = load_json(os.path.join(root, "BENCHMARK.json"))
        w = next((w for w in bench["workloads"] if w["name"] == name), None)
        if w is None:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
        c = next(c for c in bench["configs"] if c["name"] == w["config"])
        cfg = load_json(os.path.join(root, c["file"]))
        mix = generator.load_mix(os.path.join(
            root, "bench", "traffic", w["traffic"] + ".json"))
        limits = load_json(os.path.join(root, "bench", "limits",
                                        w["config"] + ".json"))
        return cls(name, int(w["chips"]), bench, cfg, mix, limits,
                   arch.of(cfg, root))

    def metrics(self, traced: bool) -> list[dict]:
        key = "per_layer" if traced else "end_to_end"
        return [m for m in self.bench[key]
                if self.name in m.get("workloads", [self.name])]

    @property
    def cascade(self) -> bool:
        return self.mix["min_confidence"] > 0


def reader(name: str):
    """``read`` of ``bench/metrics/<name>.py``."""
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class System:
    """The engine over a cell's configuration, built once; ``reseed``
    swaps in another seed's weights without recompiling anything."""

    def __init__(self, cell: Cell, seed: int):
        from bench import build
        self.cfg, self.archs = cell.cfg, cell.archs
        self.expert_params, self.router_params = build.make_weights(
            self.cfg, seed, self.archs)
        self.lib = build.library(self.cfg, self.expert_params, self.archs)
        self.eng = build.engine(self.cfg, self.lib, self.router_params)
        self.spans = Spans(self.eng)
        self.compiles = CompileCounter()

    def warm(self, cascade: bool) -> int:
        from bench import build
        return build.warm(self.eng, self.cfg, cascade)

    def reseed(self, seed: int) -> None:
        from bench import build
        from repro.core.router import VersionedParams
        from repro.serving.engine import EngineStats
        self.expert_params, self.router_params = build.make_weights(
            self.cfg, seed, self.archs)
        for e, p in zip(self.lib.experts, self.expert_params):
            e.params = p
        self.eng._router = VersionedParams(self.router_params, 0)
        self.eng.stats = EngineStats()
        if self.eng.cache is not None:
            self.eng.cache.clear()


class Spans:
    """Host spans around ``pipeline.admit`` (Route -> Cascade ->
    Fallback of one admission batch) and ``pipeline.flush`` (Execute ->
    Feedback of one lane flush), set on the engine's pipeline instance,
    as ``TraceAnnotation``s so a traced run holds them on the device
    trace's clock; admissions are also kept as (start, end, rows)."""

    def __init__(self, eng, clock=time.monotonic):
        from jax.profiler import TraceAnnotation
        from repro.serving.engine import bucket_size
        self.reset()
        pipe = eng.pipeline
        admit, flush = pipe.admit, pipe.flush
        buckets = eng.buckets

        def admit_span(reqs):
            n = len(reqs)
            b = bucket_size(n) if buckets else n
            t = clock()
            with TraceAnnotation("bench.admit", rows=n, bucket=b):
                ctx = admit(reqs)
            self.admits.append((t, clock(), n))
            return ctx

        def flush_span(expert_idx, entries, reason):
            n = len(entries)
            b = bucket_size(n) if buckets else n
            with TraceAnnotation("bench.flush", expert=expert_idx, rows=n,
                                 bucket=b):
                return flush(expert_idx, entries, reason)

        pipe.admit, pipe.flush = admit_span, flush_span

    def reset(self) -> None:
        self.admits: list[tuple[float, float, int]] = []


class CompileCounter:
    """Counts XLA compilations while ``on`` (none may fall in the
    window)."""

    def __init__(self):
        import jax
        self.on, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **_kw) -> None:
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


@dataclasses.dataclass
class Run:
    """What the metric readers read."""
    cell: Cell
    seconds: float
    setup_s: float
    due: np.ndarray            # due time per request (engine clock)
    done: np.ndarray           # time its Result was yielded (nan: none)
    failed: np.ndarray         # Result marked failed
    expert: np.ndarray         # index of the expert that served it (-1)
    opened: float              # window open (engine clock)
    close: float               # window close (engine clock)
    lateness: np.ndarray
    stats: dict                # engine counters at the window's close
    spans: Spans
    peaks: dict
    trace: object = None       # trace.Reduction of a traced run

    @property
    def latency_s(self) -> np.ndarray:
        """Due to yield, per request; a request with no Result counts
        until the end of the run."""
        end = np.nanmax(self.done) if np.isfinite(self.done).any() \
            else self.close
        return np.where(np.isfinite(self.done), self.done, end) - self.due

    def in_window(self, spans):
        return [s for s in spans
                if s[0] >= self.opened and s[1] <= self.close]


def snapshot(stats) -> dict:
    return {"padded_rows": stats.padded_rows,
            "bucket_hits": dict(stats.bucket_hits),
            "served": stats.served, "escalations": stats.escalations,
            "router_batches": stats.router_batches,
            "flushes": dict(stats.flushes), "cache_hits": stats.cache_hits}


def serve_window(system: System, cell: Cell, seconds: float, seed: int,
                 trace_dir: str | None, t_start: float, peaks: dict):
    """Serve one window of the cell's traffic.  Returns the ``Run`` and
    the served ``Result`` per uid."""
    import jax
    traffic = generator.make_traffic(cell.mix, seconds, seed)
    spans, compiles = system.spans, system.compiles
    spans.reset()
    window = None
    if trace_dir is not None:
        from bench import trace
        jax.profiler.start_trace(trace_dir,
                                 profiler_options=trace.options())
        window = jax.profiler.TraceAnnotation("bench.window")
        window.__enter__()
    t0 = time.monotonic() + 0.01
    setup_s = t0 - t_start
    closing = {}

    def on_close():
        closing["t"] = time.monotonic()
        closing["stats"] = snapshot(system.eng.stats)
        compiles.on = False
        if window is not None:
            window.__exit__(None, None, None)
            jax.profiler.stop_trace()

    wait_span = contextlib.nullcontext
    if window is not None:
        wait_span = lambda: jax.profiler.TraceAnnotation("bench.wait")  # noqa: E731
    feed = generator.OpenLoop(traffic, t0, seconds, on_close,
                              wait_span=wait_span)
    n = len(traffic.requests)
    done = np.full(n, np.nan)
    failed = np.zeros(n, bool)
    expert = np.full(n, -1)
    names = [e["name"] for e in cell.cfg["experts"]]
    results = {}
    dup = 0
    compiles.on, compiles.count = True, 0
    for res in system.eng.serve(feed):
        t = time.monotonic()
        if res.uid in results:
            dup += 1
        results[res.uid] = res
        done[res.uid] = t
        failed[res.uid] = res.failed
        expert[res.uid] = names.index(res.expert)
    run = Run(cell, seconds, setup_s,
              feed.due, done, failed,
              expert, t0, closing["t"], np.array(feed.lateness),
              closing["stats"], spans, peaks)
    return run, traffic, results, dup, compiles.count


# ------------------------------------------------------------- checking

def sample_served(results: dict, k: int,
                  rng: np.random.Generator) -> list[int]:
    """``k`` served uids drawn by the seed, with at least one per expert
    that served anything."""
    ok = sorted(u for u, r in results.items() if not r.failed)
    by_expert: dict = {}
    for u in ok:
        by_expert.setdefault(results[u].expert, []).append(u)
    pick = {int(rng.choice(v)) for _, v in sorted(by_expert.items())}
    rest = [u for u in ok if u not in pick]
    extra = max(0, k - len(pick))
    pick |= set(int(u) for u in rng.choice(rest, size=min(extra, len(rest)),
                                           replace=False)) if rest else set()
    return sorted(pick)


def expert_readings(cfg, archs, expert_params, traffic, results, uids,
                    prec):
    """Per sampled request: the widest gap of its served tokens below the
    f32 reference's best logit, and the gap of its NLL to the
    reference's; with ``prec`` other than f32 the served tokens and NLL
    are the control's (the reference in that precision), not the
    program's.  Each expert is read by its architecture's ``readings``."""
    reqs = traffic.requests
    gaps, nll_gaps = [], []
    for e, a, p in zip(cfg["experts"], archs, expert_params):
        mine = [u for u in uids if results[u].expert == e["name"]]
        if not mine:
            continue
        toks = np.stack([reqs[u].tokens for u in mine])
        tgt = np.stack([reqs[u].targets for u in mine]).astype(np.int32)
        msk = np.stack([reqs[u].mask for u in mine]).astype(np.int32)
        if prec == "f32":
            served = np.stack([results[u].predictions for u in mine])
            loss = np.array([np.nan if results[u].loss is None
                             else results[u].loss for u in mine])
        else:
            _, loss, served = reference.in_blocks(
                lambda t, s, g, m: a.readings(p, e, t, s, g, m, prec),
                REF_ROWS, toks, np.zeros_like(toks), tgt, msk)
        gap, nll, _ = reference.in_blocks(
            lambda t, s, g, m: a.readings(p, e, t, s, g, m, "f32"),
            REF_ROWS, toks, served.astype(np.int32), tgt, msk)
        gaps.append(gap.max(axis=1))
        nll_gaps.append(np.abs(loss - nll) / np.maximum(1.0, np.abs(nll)))
    return np.concatenate(gaps), np.concatenate(nll_gaps)


def router_readings(cfg, archs, router_params, traffic, results, uids,
                    prec):
    """Per sampled request: the largest gap of predicted losses to the
    f32 reference's, and the verdict margin (0 where the verdict is the
    reference's; else the least margin on the reference's own way to
    its verdict).  With ``prec`` other than f32 the predictions and
    verdicts are the control's."""
    reqs = traffic.requests
    toks = np.stack([reqs[u].tokens for u in uids])
    pred_ref, sig_ref = reference.in_blocks(
        lambda t: router.readings(router_params, t), ROUTER_ROWS, toks)
    costs = reference.constraint_costs(cfg, archs)
    lam = np.array([[reqs[u].lambdas.get(c, 0.0) for c in cfg["constraints"]]
                    for u in uids])
    order = reference.ladder(cfg, archs)
    depth = cfg["engine"]["cascade_max_depth"]
    names = [e["name"] for e in cfg["experts"]]
    if prec == "f32":
        pred = np.stack([results[u].pred_losses for u in uids])
        final = np.array([names.index(results[u].expert) for u in uids])
    else:
        pred, sig = reference.in_blocks(
            lambda t: router.readings(router_params, t, prec=prec),
            ROUTER_ROWS, toks)
        final = np.array([reference.verdict(
            pred[i] + lam[i] @ costs, 1.0 / (1.0 + sig[i]),
            reqs[u].min_confidence, order, depth)[0]
            for i, u in enumerate(uids)])
    pred_gap = np.abs(pred - pred_ref).max(axis=1)
    scores = pred_ref + lam @ costs
    conf = 1.0 / (1.0 + sig_ref.astype(np.float64))
    margins = np.zeros(len(uids))
    for i, u in enumerate(uids):
        want, margin = reference.verdict(scores[i], conf[i],
                                         reqs[u].min_confidence, order,
                                         depth)
        if final[i] != want:
            margins[i] = margin
    return pred_gap, margins


def readings(system: System, traffic, results, seed: int,
             expert_prec: str = "f32", router_prec: str = "f32") -> dict:
    """The numbers the check compares (see ``check``): the program's, or
    with a lower precision the control's, on the same sample."""
    rng = np.random.default_rng([seed, 7])
    ex = sample_served(results, EXPERT_SAMPLE, rng)
    ro = sample_served(results, ROUTER_SAMPLE, rng)
    tok_gap, nll_gap = expert_readings(system.cfg, system.archs,
                                       system.expert_params, traffic,
                                       results, ex, expert_prec)
    pred_gap, margins = router_readings(system.cfg, system.archs,
                                        system.router_params, traffic,
                                        results, ro, router_prec)
    return {"token_logit_gap": float(np.max(tok_gap)),
            "nll_rel_gap": float(np.nanmax(nll_gap)),
            "pred_loss_gap": float(np.max(pred_gap)),
            "verdict_margin": float(np.max(margins)),
            "expert_sample": len(ex), "router_sample": len(ro)}


def check(n_due: int, results: dict, dup: int, read: dict,
          limits: dict) -> dict:
    """Each compared number beside its limit: exactly one unfailed
    Result per request due (limit 0), and the readings under the
    configuration's limits."""
    missing = n_due - len(results)
    failed = sum(r.failed for r in results.values())
    out = {"missing_failed_or_duplicate":
           {"value": missing + failed + dup, "limit": 0}}
    for key in ("token_logit_gap", "nll_rel_gap", "pred_loss_gap",
                "verdict_margin"):
        out[key] = {"value": read[key], "limit": limits[key]["limit"]}
    return out


# -------------------------------------------------------------- device

def require_devices(chips: int):
    """The accelerator the run is for: a TPU with at least ``chips``
    devices, of a kind the peaks table knows.  Exits otherwise."""
    import jax
    devs = jax.devices()
    kind = devs[0].device_kind
    if devs[0].platform != "tpu":
        raise SystemExit(f"needs a TPU, found platform "
                         f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips, found "
                         f"{len(devs)}")
    try:
        peaks = flops.peaks_for(kind)
    except KeyError as e:
        raise SystemExit(str(e)) from None
    return devs[:chips], peaks


def memory_peak(devs) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def program_path(root: str) -> None:
    """Put the program (``src/``) on the import path, or exit."""
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"no program at {src}")
    if src not in sys.path:
        sys.path.insert(0, src)
