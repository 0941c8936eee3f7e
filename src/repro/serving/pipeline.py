"""The staged serving pipeline: Route -> Cascade -> Execute -> Feedback.

``TryageEngine`` used to hard-wire this flow inside ``_route_admitted``
and ``_execute``; this module makes each stage an explicit object over a
shared typed context, so the flow reads top-to-bottom and new stages
(the Feedback stage that closes the online-adaptation loop is the first
beneficiary) slot in without touching the scheduler or the disciplines.

Two context types, matching the engine's two batch granularities:

* ``RouteContext`` — one *admission batch* flowing Route -> Cascade.
  Route fills router predictions and raw expert choices (cache-aware:
  hits skip scoring, misses are scored as one smaller batch); Cascade
  applies the abstention/escalation rule to freshly scored rows and
  memoises the post-cascade verdict.
* ``FlushContext`` — one *per-expert micro-batch* flowing Execute ->
  Feedback.  Execute launches the padded expert forward and materialises
  ``Result``s; Feedback publishes each observed (prompt, expert, loss)
  sample to the engine's replay buffer and gives the adaptation loop a
  chance to refresh the router.

Stages are deliberately thin orchestration over the engine's compute
primitives (``_score_batch``, ``_cascade``, ``_run_expert`` — the jit'd
functions live on the engine so compilation caches survive across
batches).  The split point between the halves is the scheduler: routed
requests wait in per-expert lanes between ``admit`` and ``flush``, so
Execute runs on micro-batches that mix requests from many admission
batches.

Behaviour contract: with adaptation disabled (``adapt_every=0``) and
``min_confidence=0`` the pipeline reproduces the pre-pipeline engine
bit-for-bit — identical choices, Results and EngineStats
(tests/test_pipeline.py enforces this against a reference
implementation of the old hard-wired flow).
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
from typing import TYPE_CHECKING

import numpy as np

from repro.serving import tracing
from repro.serving.cache import DecisionCache
from repro.serving.requests import Request, Result
from repro.serving.scheduler import LaneEntry

if TYPE_CHECKING:                                      # pragma: no cover
    from repro.serving.engine import TryageEngine


@dataclasses.dataclass
class RouteContext:
    """One admission batch flowing Route -> Cascade.

    ``pred``/``choice``/``cached``/``depth``/``confidence`` are dense
    per-request arrays (allocated by RouteStage); ``miss_idx`` lists the
    rows that were freshly scored this batch — the only rows Cascade
    touches, because cache hits already carry their post-cascade
    verdict.  ``keys`` holds the decision-cache keys (None when the
    cache is disabled).
    """

    reqs: list[Request]
    pred: np.ndarray | None = None          # (B, M) f32 router L-hat
    choice: np.ndarray | None = None        # (B,) i64 expert index
    cached: np.ndarray | None = None        # (B,) bool cache hits
    depth: np.ndarray | None = None         # (B,) i64 cascade depth
    confidence: np.ndarray | None = None    # (B,) f64 final confidence
    fallback_depth: np.ndarray | None = None  # (B,) i64 health fallbacks
    keys: list | None = None
    miss_idx: list[int] = dataclasses.field(default_factory=list)
    # row -> pooled router embedding, filled only when the semantic tier
    # is enabled (Cascade feeds these back into T3 at memoisation time)
    emb: dict | None = None
    # one-launch cascade payload: (rows, sigma, esc) when the Route
    # stage scored the misses through the fused cascade kernel — rows
    # lists the scored ctx indices (== miss_idx), sigma/esc are the
    # kernel's per-expert uncertainty and depth-1 escalation target
    # aligned with it.  None = staged scoring, Cascade runs the
    # sigma pass itself.
    fused: tuple | None = None
    admit_id: int = -1                      # links the batch's spans


@dataclasses.dataclass
class FlushContext:
    """One per-expert micro-batch flowing Execute -> Feedback;
    ``start`` is the flush's start on the engine clock."""

    expert_idx: int
    entries: list[LaneEntry]
    reason: str
    start: float
    results: list[Result] = dataclasses.field(default_factory=list)


class RouteStage:
    """Score an admission batch through the decision cache.

    Hits return their memoised post-cascade verdict; misses are scored
    as one (smaller) batch with the router.  The cache key carries the
    live router version (``engine.router_version``), so verdicts scored
    by a superseded router can never hit."""

    def __init__(self, engine: "TryageEngine"):
        self.eng = engine

    def __call__(self, ctx: RouteContext) -> RouteContext:
        eng = self.eng
        B = len(ctx.reqs)
        ctx.pred = np.zeros((B, eng.rc.n_models), np.float32)
        ctx.choice = np.zeros(B, np.int64)
        ctx.cached = np.zeros(B, bool)
        ctx.depth = np.zeros(B, np.int64)
        ctx.confidence = np.ones(B, np.float64)
        ctx.fallback_depth = np.zeros(B, np.int64)
        if eng.cache is None:
            pred, choice = self._score_rows(ctx, list(range(B)))
            ctx.pred[:] = pred
            ctx.choice[:] = choice
            ctx.miss_idx = list(range(B))
            return ctx
        sink = self._dropped_lambda_sink
        misses = []
        with tracing.span("admit.cache"):
            ctx.keys = [DecisionCache.key(r.tokens, r.lambdas, eng._cnames,
                                          r.min_confidence,
                                          eng.router_version,
                                          unknown_sink=sink)
                        for r in ctx.reqs]
            for i, key in enumerate(ctx.keys):
                hit, tier = eng.cache.lookup(key)
                if hit is None:
                    misses.append(i)
                else:
                    (ctx.pred[i], ctx.choice[i], ctx.depth[i],
                     ctx.confidence[i]) = hit
                    ctx.cached[i] = True
                    eng.stats.cache_tier_hits[tier] += 1
        if misses and getattr(eng.cache, "semantic", None) is not None:
            misses = self._semantic_probe(ctx, misses)
        if misses:
            if ctx.emb is not None:
                # embeddings already computed for the T3 probe: finish
                # the score from them (head + host constraint argmin)
                mpred, mchoice = eng._score_from_emb(
                    [ctx.reqs[i] for i in misses],
                    np.stack([ctx.emb[i] for i in misses]))
            else:
                mpred, mchoice = self._score_rows(ctx, misses)
            for j, i in enumerate(misses):
                ctx.pred[i] = mpred[j]
                ctx.choice[i] = mchoice[j]
        ctx.miss_idx = misses
        eng.stats.cache_hits += B - len(misses)
        eng.stats.cache_misses += len(misses)
        return ctx

    def _score_rows(self, ctx: RouteContext, rows: list[int]):
        """Score the given ctx rows as one batch — through the fused
        cascade kernel when the engine and the batch qualify (the
        sigma/escalation payload rides along on ``ctx.fused`` for the
        Cascade stage), through ``_score_batch`` otherwise."""
        eng = self.eng
        reqs = [ctx.reqs[i] for i in rows]
        if eng._use_fused_cascade(reqs):
            pred, choice, sigma, esc = eng._score_cascade_batch(reqs)
            ctx.fused = (list(rows), sigma, esc)
            return pred, choice
        return eng._score_batch(reqs)

    def _dropped_lambda_sink(self, names: list) -> None:
        self.eng.stats.cache_key_dropped_lambda += len(names)

    def _semantic_probe(self, ctx: RouteContext,
                        misses: list[int]) -> list[int]:
        """T3 pass over the exact-miss rows: one batched embedding pass,
        then a nearest-neighbour probe per row.  A hit adopts the
        cached post-cascade verdict (after revalidation against the
        live router version — see ``semcache.SemanticCache``); the
        remaining rows keep their embeddings in ``ctx.emb`` so scoring
        and T3 insertion reuse the encoder pass."""
        eng = self.eng
        emb = eng._embed_batch([ctx.reqs[i] for i in misses])
        ctx.emb = {i: emb[j] for j, i in enumerate(misses)}
        still = []
        for j, i in enumerate(misses):
            entry, status = eng.cache.lookup_semantic(
                emb[j], ctx.keys[i], eng.router_version)
            if status != "miss":
                eng.stats.cache_revalidations += 1
            if status == "hit":
                (ctx.pred[i], ctx.choice[i], ctx.depth[i],
                 ctx.confidence[i]) = entry
                ctx.cached[i] = True
                eng.stats.cache_tier_hits["t3"] += 1
                # promote into the exact tiers under this prompt's own
                # key: the next identical retry is a T1 hit, no
                # embedding pass needed
                eng.cache.put(ctx.keys[i], entry[0], entry[1],
                              int(entry[2]), float(entry[3]))
                continue
            if status == "stale":
                eng.stats.cache_revalidation_rejects += 1
            still.append(i)
        return still


class CascadeStage:
    """Apply the abstention/escalation rule to freshly scored rows and
    memoise the post-cascade verdict.

    Only ``miss_idx`` rows are cascaded — cache hits were stored *after*
    their cascade, so re-running it would double-escalate.  The
    single-shot fast path (no request carries a confidence floor) is
    inherited from ``engine._cascade``: the sigma pass is skipped and
    choices pass through untouched."""

    def __init__(self, engine: "TryageEngine"):
        self.eng = engine

    def __call__(self, ctx: RouteContext) -> RouteContext:
        if not ctx.miss_idx:
            return ctx
        with tracing.span("admit.cascade", admit_id=ctx.admit_id):
            return self._cascade(ctx)

    def _cascade(self, ctx: RouteContext) -> RouteContext:
        eng = self.eng
        miss_reqs = [ctx.reqs[i] for i in ctx.miss_idx]
        mpred = ctx.pred[ctx.miss_idx]
        if ctx.fused is not None and ctx.fused[0] == ctx.miss_idx:
            # the Route stage already has sigma and the depth-1
            # escalation target from the fused kernel — resolve the
            # verdict without a second router pass
            _, sigma, esc = ctx.fused
            mchoice, mdepth, mconf = eng._cascade_fused(
                miss_reqs, mpred, ctx.choice[ctx.miss_idx], sigma, esc)
        else:
            mchoice, mdepth, mconf = eng._cascade(
                miss_reqs, mpred, ctx.choice[ctx.miss_idx])
        for j, i in enumerate(ctx.miss_idx):
            ctx.choice[i] = mchoice[j]
            ctx.depth[i] = mdepth[j]
            ctx.confidence[i] = mconf[j]
            if ctx.keys is not None:
                if ctx.emb is not None:
                    # semantic tier enabled: hand the row's embedding to
                    # the stack so T3 learns this verdict too
                    eng.cache.put(ctx.keys[i], mpred[j], mchoice[j],
                                  int(mdepth[j]), float(mconf[j]),
                                  emb=ctx.emb[i])
                else:
                    eng.cache.put(ctx.keys[i], mpred[j], mchoice[j],
                                  int(mdepth[j]), float(mconf[j]))
        return ctx


class FallbackStage:
    """Health consult: walk the fallback chain for requests whose chosen
    expert is unhealthy or saturated (``core.objective.fallback_choice``
    over ``engine.health``'s availability mask).

    Runs *after* the cache/cascade half on every row — cache hits
    included, because health is time-varying state that must never be
    memoised: the cache stores the pre-fallback verdict and this stage
    re-applies the current health picture to it.  With no health tracker
    attached (``engine.health is None``, the default) or with every
    expert available, the stage is a strict no-op — the parity contract
    with the health-unaware engine (tests/test_fallback.py) holds by
    construction."""

    def __init__(self, engine: "TryageEngine"):
        self.eng = engine

    def __call__(self, ctx: RouteContext) -> RouteContext:
        eng = self.eng
        if eng.health is None or eng.fallback_max_depth <= 0:
            return ctx
        with tracing.span("admit.fallback", admit_id=ctx.admit_id):
            return self._fallback(ctx)

    def _fallback(self, ctx: RouteContext) -> RouteContext:
        eng = self.eng
        avail = eng.health.available_mask()
        if avail.all():
            return ctx
        from repro.core.objective import fallback_choice
        from repro.serving.requests import lambda_matrix
        healthy = eng.health.healthy_mask()
        # the same constrained objective the Route stage minimised:
        # L-hat + sum_j lambda_j C_j, per request
        scores = ctx.pred + lambda_matrix(ctx.reqs, eng._cnames) @ eng._cmat
        for i in range(len(ctx.reqs)):
            final, fdepth, degraded = fallback_choice(
                scores[i], healthy, avail, int(ctx.choice[i]),
                eng._esc_order, eng.fallback_max_depth)
            if fdepth == 0:
                continue
            ctx.choice[i] = final
            ctx.fallback_depth[i] = fdepth
            eng.stats.fallbacks += 1
            eng.stats.fallback_depth_hist[fdepth] += 1
            if degraded:
                eng.stats.degraded += 1
        return ctx


class ExecuteStage:
    """Launch one padded per-expert micro-batch and materialise Results
    with true enqueue->flush latency; all execution telemetry
    (flushes, buckets, latencies, cascade histogram) lands here.

    On a mesh-backed engine the launch is a *dispatch*:
    ``engine._run_expert`` consults the placement map
    (``serving.placement.PlacementMap``) and commits the micro-batch to
    the least-busy device stream among the expert's replica slices —
    the stage itself is device-agnostic, which is exactly why the
    executor could be swapped under it without touching the flow."""

    def __init__(self, engine: "TryageEngine"):
        self.eng = engine

    def __call__(self, ctx: FlushContext) -> FlushContext:
        eng = self.eng
        e = eng.library[ctx.expert_idx]
        t0 = ctx.start
        preds, ex_loss, ex_acc = eng._run_expert(
            e, [en.req for en in ctx.entries])
        end = eng._now()
        eng.stats.expert_time_s += end - t0
        eng.stats.flushes[ctx.reason] += 1
        with tracing.span("flush.results"):
            for j, en in enumerate(ctx.entries):
                r = en.req
                loss = acc = None
                if (r.targets is not None and r.mask is not None
                        and r.mask.astype(bool).any()):
                    loss = float(ex_loss[j])
                    acc = float(ex_acc[j])
                flops = 2.0 * e.n_params * len(r.tokens)
                latency = (max(end - r.arrival, 0.0) if r.arrival is not None
                           else end - t0)
                ctx.results.append(Result(
                    uid=r.uid, expert=e.name, pred_losses=en.pred,
                    predictions=preds[j], loss=loss, accuracy=acc,
                    flops_proxy=flops, latency_s=latency, cached=en.cached,
                    flush_reason=ctx.reason, cascade_depth=en.depth,
                    confidence=en.confidence,
                    fallback_depth=en.fallback_depth))
                eng.stats.served += 1
                eng.stats.per_expert[e.name] += 1
                eng.stats.total_flops += flops
                eng.stats.latencies.append(latency)
                eng.stats.cascade_depth_hist[en.depth] += 1
                eng.stats.tier_latencies[en.depth].append(latency)
                if en.depth > 0:
                    eng.stats.escalations += 1
        return ctx


class FeedbackStage:
    """Close the loop: publish each observed (prompt, expert, loss)
    sample to the replay buffer and let the adaptation loop refresh the
    router.

    A sample is published only when the expert's loss was actually
    measured (``Result.loss`` is not None — the request carried MLM
    targets); samples whose token shape does not match the buffer's are
    dropped and counted (mixed-length traffic serves fine, it just
    cannot all feed one replay batch).  ``engine._maybe_adapt`` is a
    no-op unless the engine was built with ``adapt_every > 0``, so the
    feedback stage is free for frozen-router serving."""

    def __init__(self, engine: "TryageEngine"):
        self.eng = engine

    def __call__(self, ctx: FlushContext) -> FlushContext:
        with tracing.span("flush.feedback"):
            return self._feedback(ctx)

    def _feedback(self, ctx: FlushContext) -> FlushContext:
        eng = self.eng
        if eng.replay is None:
            return ctx
        for en, res in zip(ctx.entries, ctx.results):
            if res.loss is None:
                continue
            eng.replay.add(en.req.tokens, ctx.expert_idx, res.loss)
        eng.stats.feedback_events = eng.replay.seen
        eng.stats.feedback_dropped = eng.replay.dropped
        eng.stats.replay_len = len(eng.replay)
        eng.stats.replay_cap = eng.replay.capacity
        eng._maybe_adapt()
        return ctx


class ServingPipeline:
    """The five stages composed over one engine.

    ``admit``  runs Route -> Cascade -> Fallback on an admission batch
               and returns the filled RouteContext (the engine pushes
               the rows into scheduler lanes, or executes them directly
               under FIFO).  Fallback is a strict no-op without a
               health tracker, so the health-unaware pipeline is still
               the PR-4 Route -> Cascade flow bit-for-bit.
    ``flush``  runs Execute -> Feedback on one per-expert micro-batch
               and returns its Results.

    Both stamp their start on the engine clock: each admitted row's
    queue wait (admission start - ``Request.arrival``) and each flushed
    row's lane wait (flush start - ``LaneEntry.pushed``) land in
    ``EngineStats``, and under a profiler session in the ``tryage.admit``
    / ``tryage.flush`` spans (see ``serving.tracing``), with the uids.
    """

    def __init__(self, engine: "TryageEngine"):
        self.eng = engine
        self.route = RouteStage(engine)
        self.cascade = CascadeStage(engine)
        self.fallback = FallbackStage(engine)
        self.execute = ExecuteStage(engine)
        self.feedback = FeedbackStage(engine)
        self._admit_ids = itertools.count()
        self._flush_ids = itertools.count()

    @contextlib.contextmanager
    def admission(self, reqs: list[Request]):
        """The admission batch's context, inside its ``admit`` span;
        records the rows' queue waits."""
        eng = self.eng
        t = eng._now()
        ctx = RouteContext(reqs, admit_id=next(self._admit_ids))
        n = len(reqs)
        with tracing.span("admit", start=t, admit_id=ctx.admit_id, rows=n,
                          bucket=eng._bucket(n)) as sp:
            waits = [t - r.arrival if r.arrival is not None
                     else float("nan") for r in reqs]
            eng.stats.queue_waits.extend(w for w in waits if w == w)
            if sp:
                sp.set(uids=[r.uid for r in reqs], waits=waits)
            yield ctx

    def admit(self, reqs: list[Request]) -> RouteContext:
        with self.admission(reqs) as ctx:
            return self.fallback(self.cascade(self.route(ctx)))

    def flush(self, expert_idx: int, entries: list[LaneEntry],
              reason: str) -> list[Result]:
        eng = self.eng
        t = eng._now()
        n = len(entries)
        with tracing.span("flush", start=t, flush_id=next(self._flush_ids),
                          expert=expert_idx, rows=n, bucket=eng._bucket(n),
                          reason=reason) as sp:
            waits = [t - en.pushed if en.pushed is not None
                     else float("nan") for en in entries]
            eng.stats.lane_waits.extend(w for w in waits if w == w)
            if sp:
                sp.set(uids=[en.req.uid for en in entries], waits=waits)
            trips = eng.stats.flush_round_trips
            ctx = FlushContext(expert_idx, entries, reason, t)
            results = self.feedback(self.execute(ctx)).results
            if sp:
                sp.set(round_trips=eng.stats.flush_round_trips - trips)
            return results
