"""The Tryage serving engine: an explicit staged pipeline

    Route -> Cascade -> Execute -> Feedback

over a model library (stages in ``repro.serving.pipeline``).

This is the production form of the paper's dispatch loop: requests are
admitted, the perceptive router scores a whole admission batch in one
forward pass (Route), the routing objective (with per-request lambda
weights from user flags) picks an expert per prompt and the
confidence cascade may escalate it (Cascade), and prompts land in
per-expert *lanes* owned by the scheduler; lane flushes run the expert
(Execute) and publish the observed loss back to the router's replay
buffer (Feedback).  Two executor disciplines exist on top of the same
routing stages:

  ``run()``    FIFO drain — every admission batch launches its per-expert
               groups immediately, however ragged.  Kept as the baseline
               the continuous-batching path is benchmarked against.
  ``serve()``  continuous batching — lanes accumulate same-expert
               requests *across* admission batches and flush only on a
               full power-of-two bucket or a ``max_wait_s`` deadline
               (see ``repro.serving.scheduler``), streaming ``Result``s
               back as micro-batches complete.

Routing decisions are memoised in an exact LRU cache keyed on
``(token bytes, lambda vector, confidence threshold, router version)``
(``repro.serving.cache``), so repeated prompts skip the router forward
pass entirely; a hit returns the identical (post-cascade) verdict the
fresh score produced, and a router-version bump makes every older
verdict unreachable.

Confidence-aware cascade: a request may carry ``min_confidence > 0``.
After scoring, the router's per-expert uncertainty head (constant prior
for pre-cascade checkpoints) yields a calibrated confidence per expert;
if the chosen expert's confidence is below the threshold, the request
is *escalated* — re-enqueued into the scheduler's escalation lane for
the next-larger expert (``core.objective.cascade_choice``, bounded
depth, cycle-safe) instead of flushing with its first pick.  Cascade
telemetry (escalations, depth histogram, per-tier latency) lands in
``EngineStats``.  ``min_confidence = 0`` (the default) is single-shot:
the sigma pass is skipped entirely and behaviour is identical to the
pre-cascade engine.

Online adaptation: the paper's router *continually tracks downstream
expert performance*, so the engine can close the loop at serving time.
Expert execution already measures the chosen expert's true masked NLL;
the Feedback stage publishes those (prompt, expert, loss) samples onto
a bounded replay buffer (``repro.serving.feedback``), and every
``adapt_every`` samples the engine replays a batch through the jit'd
incremental update built by ``core.training.make_router_update_step``
on *shadow weights* — in-flight scoring keeps reading the complete old
tree, and
the refreshed parameters are published atomically via
``core.router.VersionedParams.swap``.  Each swap bumps the router
``version``, which is part of the decision-cache key, so verdicts
scored by a superseded router are structurally unreachable (the cache
is also cleared to reclaim their memory).  ``adapt_every=0`` (the
default) freezes the router and the engine behaves exactly like the
pre-adaptation engine, bit-for-bit.

Two decision paths exist for the scoring itself:

  use_kernel=True   one jit'd decision function per batch: the encoder
                    embedding runs in XLA, then MLP head -> softplus ->
                    lambda-weighted constraint add -> argmin run fused in
                    the Pallas kernel (``router_score_fused`` via
                    ``ops.router_route``), compiled on TPU/GPU, interpret
                    fallback on CPU.  No host round-trip between scoring
                    and selection.
  use_kernel=False  reference path: XLA head + NumPy constraint add on
                    the host (kept for parity checks and benchmarking).

Expert micro-batches are padded to power-of-two buckets (``buckets=True``)
so the jit'd expert functions see a bounded set of shapes instead of
recompiling for every ragged batch size; bucket occupancy, flush
reasons, cache hit rate and per-request latency percentiles are tracked
in ``EngineStats``.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import time
from collections import defaultdict, deque
from typing import Callable, Iterable, Iterator, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.library import ModelLibrary
from repro.core.objective import (Constraint, cascade_choice,
                                  confidence_scores, constraint_matrix,
                                  escalation_order, fallback_choice)
from repro.core.router import (RouterConfig, VersionedParams,
                               losses_from_emb, predict_losses,
                               predict_uncertainty, router_embed)
from repro.core.training import (make_router_update_step,
                                 router_prediction_error)
from repro.kernels import sanitize
from repro.kernels.router_cascade import ops as rc_ops
from repro.kernels.router_score import ops as rs_ops
from repro.models.model import forward
from repro.serving.cache import DecisionCache, DecisionCacheStack
from repro.serving.semcache import SemanticCache
from repro.serving.feedback import ReplayBuffer
from repro.serving.health import ExpertHealth
from repro.serving.pipeline import RouteContext, ServingPipeline
from repro.serving.placement import (PlacementMap, StreamClock,
                                     plan_placement)
from repro.serving.requests import Request, Result, lambda_matrix
from repro.serving.scheduler import ExpertScheduler, LaneEntry
from repro.sharding.context import (activation_sharding, batch_sharding,
                                    replicated_sharding)
from repro.sharding.rules import DEFAULT_RULES


def bucket_size(n: int) -> int:
    """Smallest power of two >= n — the padded micro-batch shape."""
    return 1 << (n - 1).bit_length() if n > 1 else 1


@dataclasses.dataclass
class EngineStats:
    served: int = 0
    per_expert: dict = dataclasses.field(
        default_factory=lambda: defaultdict(int))
    total_flops: float = 0.0
    router_time_s: float = 0.0
    router_batches: int = 0            # router forward passes launched
    expert_time_s: float = 0.0
    # shape-bucketing telemetry: padded micro-batch size -> launch count,
    # plus the total number of padded (wasted) rows executed.
    bucket_hits: dict = dataclasses.field(
        default_factory=lambda: defaultdict(int))
    padded_rows: int = 0
    # scheduler telemetry: flush reason -> count, peak lane depth per
    # expert name, and true enqueue->flush latency per request.
    flushes: dict = dataclasses.field(
        default_factory=lambda: defaultdict(int))
    lane_peaks: dict = dataclasses.field(default_factory=dict)
    # bounded window so a long-running serve() keeps O(1) memory;
    # percentiles are over the most recent 64k requests
    latencies: deque = dataclasses.field(
        default_factory=lambda: deque(maxlen=65536))
    # router-decision cache telemetry.  Tier attribution: "t1" is the
    # in-process exact LRU, "t2" the persistent KV store, "t3" the
    # semantic tier.  Revalidations count semantic candidates found
    # within the distance bound (then version-checked); rejects are the
    # candidates that failed the check (stale router version).
    # cache_key_dropped_lambda counts request lambda flags whose names
    # matched no engine constraint (dropped from the cache key, and
    # from scoring, by design — the count makes the typo visible).
    cache_hits: int = 0
    cache_misses: int = 0
    cache_tier_hits: dict = dataclasses.field(
        default_factory=lambda: defaultdict(int))
    cache_revalidations: int = 0
    cache_revalidation_rejects: int = 0
    cache_key_dropped_lambda: int = 0
    # cascade telemetry: escalated-request count, histogram of cascade
    # depth over all served requests (depth 0 = first pick), and true
    # enqueue->flush latency bucketed by cascade tier.
    escalations: int = 0
    cascade_depth_hist: dict = dataclasses.field(
        default_factory=lambda: defaultdict(int))
    tier_latencies: dict = dataclasses.field(
        default_factory=lambda: defaultdict(
            lambda: deque(maxlen=65536)))
    # speculative-escalation telemetry (serve() with speculate=True):
    # lane entries enqueued before their escalation verdict resolved,
    # split into confirmed first picks (hits), entries pulled back out
    # of their lane before flushing (cancelled), and entries whose
    # speculative execution had to be discarded (wasted, with the token
    # count of the discarded work).  Exactly-once invariant:
    # launched == hits + cancelled + wasted once all verdicts resolve.
    spec_launched: int = 0
    spec_hits: int = 0
    spec_cancelled: int = 0
    spec_wasted: int = 0
    spec_wasted_tokens: int = 0
    # effective launch geometry of the fused decision kernel per padded
    # admission-batch size (the tile that actually ran after the
    # block_b = min(block_b, B) clamp) and its launch count, which says
    # how often a batch spans more than one kernel tile
    router_tiles: dict = dataclasses.field(default_factory=dict)
    # online-adaptation telemetry: router updates applied (and the
    # resulting router version), feedback samples published, replay
    # occupancy, wall time spent in update steps, and the mean
    # |L-hat[chosen] - L_observed| on the last replayed batch before and
    # after its update (the adaptation loop's health signal: post < pre
    # means the update moved predictions toward observed reality).
    adapt_updates: int = 0
    router_version: int = 0
    feedback_events: int = 0
    feedback_dropped: int = 0
    replay_len: int = 0
    replay_cap: int = 0
    adapt_time_s: float = 0.0
    adapt_pre_err: float = 0.0
    adapt_post_err: float = 0.0
    # serving-front-end telemetry: concurrent sessions multiplexed, total
    # requests admitted through the bounded queue, load-shed requests
    # (total and per Request.priority), and the queue's peak occupancy.
    sessions: int = 0
    admitted: int = 0
    shed: int = 0
    shed_by_priority: dict = dataclasses.field(
        default_factory=lambda: defaultdict(int))
    admission_queue_peak: int = 0
    # health-fallback telemetry: route-time fallback re-selections (with
    # a depth histogram and the graceful-degraded subset), failed-flush
    # re-routes, requests failed outright (no fallback available), and
    # failed flushes per expert name.
    fallbacks: int = 0
    fallback_depth_hist: dict = dataclasses.field(
        default_factory=lambda: defaultdict(int))
    degraded: int = 0
    reroutes: int = 0
    failed: int = 0
    expert_failures: dict = dataclasses.field(
        default_factory=lambda: defaultdict(int))

    @property
    def cache_hit_rate(self) -> float:
        total = self.cache_hits + self.cache_misses
        return self.cache_hits / total if total else 0.0

    @staticmethod
    def _pctiles(latencies) -> dict:
        if not latencies:
            return {"p50_s": 0.0, "p95_s": 0.0}
        lat = np.asarray(latencies)
        return {"p50_s": float(np.percentile(lat, 50)),
                "p95_s": float(np.percentile(lat, 95))}

    def latency_percentiles(self) -> dict:
        return self._pctiles(self.latencies)

    def tier_latency_percentiles(self) -> dict:
        """p50/p95 enqueue->flush latency per cascade tier (depth)."""
        return {int(tier): self._pctiles(lat)
                for tier, lat in sorted(self.tier_latencies.items())}

    def summary(self) -> dict:
        return {"served": self.served,
                "per_expert": dict(self.per_expert),
                "total_flops": self.total_flops,
                "router_time_s": round(self.router_time_s, 3),
                "router_batches": self.router_batches,
                "expert_time_s": round(self.expert_time_s, 3),
                "bucket_hits": {int(k): v for k, v in
                                sorted(self.bucket_hits.items())},
                "padded_rows": self.padded_rows,
                "flushes": dict(self.flushes),
                "lane_peaks": dict(self.lane_peaks),
                "latency": {k: round(v, 6) for k, v in
                            self.latency_percentiles().items()},
                "cache": {"hits": self.cache_hits,
                          "misses": self.cache_misses,
                          "hit_rate": round(self.cache_hit_rate, 4),
                          "tiers": {k: int(v) for k, v in
                                    sorted(self.cache_tier_hits.items())},
                          "revalidations": self.cache_revalidations,
                          "revalidation_rejects":
                              self.cache_revalidation_rejects,
                          "dropped_lambda":
                              self.cache_key_dropped_lambda},
                "cascade": {
                    "escalations": self.escalations,
                    "depth_hist": {int(k): v for k, v in
                                   sorted(self.cascade_depth_hist.items())},
                    "tier_latency": {
                        tier: {k: round(v, 6) for k, v in p.items()}
                        for tier, p in
                        self.tier_latency_percentiles().items()}},
                "speculation": {
                    "launched": self.spec_launched,
                    "hits": self.spec_hits,
                    "cancelled": self.spec_cancelled,
                    "wasted": self.spec_wasted,
                    "wasted_tokens": self.spec_wasted_tokens},
                "router_tiles": {int(k): dict(v) for k, v in
                                 sorted(self.router_tiles.items())},
                "adaptation": {
                    "updates": self.adapt_updates,
                    "router_version": self.router_version,
                    "feedback_events": self.feedback_events,
                    "feedback_dropped": self.feedback_dropped,
                    "replay": {"len": self.replay_len,
                               "cap": self.replay_cap},
                    "pre_err": round(self.adapt_pre_err, 6),
                    "post_err": round(self.adapt_post_err, 6),
                    "time_s": round(self.adapt_time_s, 3)},
                "frontend": {
                    "sessions": self.sessions,
                    "admitted": self.admitted,
                    "shed": self.shed,
                    "shed_by_priority": {int(k): v for k, v in
                                         sorted(self.shed_by_priority
                                                .items())},
                    "queue_peak": self.admission_queue_peak},
                "fallback": {
                    "fallbacks": self.fallbacks,
                    "depth_hist": {int(k): v for k, v in
                                   sorted(self.fallback_depth_hist
                                          .items())},
                    "degraded": self.degraded,
                    "reroutes": self.reroutes,
                    "failed": self.failed,
                    "expert_failures": dict(self.expert_failures)}}


class TryageEngine:
    """Staged serving pipeline (Route -> Cascade -> Execute -> Feedback)
    over a model library.

    Scheduler knobs (used by ``serve()``):

    - ``lane_target``: lane occupancy that flushes a full micro-batch;
      defaults to ``bucket_size(max_batch)`` so a target flush is a full
      power-of-two bucket with zero padded rows.
    - ``max_wait_s``: deadline for the oldest request in a lane — a lane
      holding even a single request flushes once it has waited this long.
    - ``decision_cache`` / ``cache_capacity``: exact LRU memoisation of
      routing decisions keyed on (token bytes, lambda vector,
      confidence threshold, router version).
    - ``cache_kv`` / ``cache_dir``: persistent exact cache tier (T2)
      behind the Valkey-shaped KV interface (``serving.kvstore``) —
      inject a store, or point ``cache_dir`` at a directory for the
      crash-safe disk default.  Restart-safe: same dir + same router
      version = warm cache.
    - ``cache_semantic_eps`` / ``cache_semantic_cap``: approximate
      cache tier (T3) keyed on router embeddings; ``eps > 0`` enables
      it (calibrate with ``serving.semcache.calibrate_eps`` or
      ``bench_cache``).  Verdicts are revalidated against the live
      router version before use.
    - ``cascade_max_depth``: bound on escalation steps per request; 0
      disables the cascade engine-wide regardless of request thresholds.
    - ``fused_cascade``: resolve scoring, confidence and the depth-1
      escalation verdict in ONE kernel launch
      (``kernels.router_cascade``) for batches that carry cascade
      traffic.  Needs ``use_kernel=True``, an uncertainty head on the
      router params, and a single-data-shard engine; otherwise (and for
      batches with no confidence floors) the staged path runs
      unchanged, so the flag degrades to a no-op instead of an error.
      Depth >= 2 escalations fall back to the staged host walk row by
      row, so verdicts match the staged path by construction.
    - ``speculate``: in ``serve()``, enqueue each cascade-eligible
      request's *first pick* lane entry immediately and resolve the
      escalation verdict on the next scheduler tick — lane occupancy
      and deadline clocks see the request while its verdict is in
      flight.  On escalate the entry is cancelled out of its lane (or
      its already-executed speculative result is discarded and counted
      as wasted) and re-laned to the escalation target.  Exactly-once:
      every request still yields exactly one Result.  Ignored when a
      health tracker is attached (fallback must see final choices) and
      under ``run()``.  Off (the default) is byte-identical to the
      non-speculative engine.
    - ``now_fn``: engine clock (injectable for deterministic tests).

    Online-adaptation knobs (used by the Feedback stage):

    - ``adapt_every``: feedback samples between router updates; 0 (the
      default) freezes the router — no updates, ever.
    - ``adapt_lr`` / ``adapt_ema`` / ``adapt_batch`` /
      ``adapt_trainable``: the incremental update recipe (see
      ``core.training.make_router_update_step``); ``"head"`` adapts the
      loss head only (the stable default), ``"all"`` also fine-tunes
      the encoder.
    - ``replay_cap``: bounded replay-buffer capacity; 0 disables
      feedback collection entirely.
    """

    def __init__(self, library: ModelLibrary, router_params,
                 rc: RouterConfig, constraints: Sequence[Constraint] = (),
                 max_batch: int = 16, use_kernel: bool = False,
                 interpret: bool | None = None, buckets: bool = True,
                 lane_target: int | None = None, max_wait_s: float = 0.05,
                 decision_cache: bool = True, cache_capacity: int = 4096,
                 cache_kv=None, cache_dir: str | None = None,
                 cache_semantic_eps: float = 0.0,
                 cache_semantic_cap: int = 65536,
                 cascade_max_depth: int = 2,
                 fused_cascade: bool = False, speculate: bool = False,
                 adapt_every: int = 0, adapt_lr: float = 1e-2,
                 adapt_ema: float = 0.0, adapt_batch: int = 32,
                 adapt_trainable: str = "head", replay_cap: int = 4096,
                 adapt_seed: int = 0,
                 health: ExpertHealth | None = None,
                 fallback_max_depth: int = 2,
                 mesh=None, placement: PlacementMap | None = None,
                 replicate_hot: int = 0,
                 now_fn: Callable[[], float] = time.monotonic):
        assert len(library) == rc.n_models
        if health is not None:
            assert health.n_experts == len(library), \
                "health tracker sized for a different library"
        self.library = library
        # the served router is a versioned snapshot: online adaptation
        # computes new weights off to the side and publishes them with
        # an atomic swap that bumps the version (and the cache keys)
        self._router = VersionedParams(router_params, 0)
        self.rc = rc
        self.constraints = list(constraints)
        self.max_batch = max_batch
        self.use_kernel = use_kernel
        self.buckets = buckets
        self.lane_target = (bucket_size(max_batch) if lane_target is None
                            else lane_target)
        self.max_wait_s = max_wait_s
        # decision cache: exact-only traffic gets the plain LRU (the
        # pre-stack engine, bit-for-bit); enabling the persistent or
        # semantic tier builds the stack.  cache_kv injects a KVStore
        # (e.g. a shared MemoryKVStore across replicas, or a real
        # Valkey adapter); cache_dir builds the crash-safe DiskKVStore.
        if decision_cache:
            kv = cache_kv
            if kv is None and cache_dir is not None:
                from repro.serving.kvstore import DiskKVStore
                kv = DiskKVStore(cache_dir)
            sem = (SemanticCache(cache_semantic_eps, cache_semantic_cap)
                   if cache_semantic_eps > 0.0 else None)
            if kv is not None or sem is not None:
                self.cache = DecisionCacheStack(cache_capacity, kv=kv,
                                                semantic=sem)
            else:
                self.cache = DecisionCache(cache_capacity)
        else:
            self.cache = None
        self.cascade_max_depth = cascade_max_depth
        self.fused_cascade = fused_cascade
        self.speculate = speculate
        self._esc_order = escalation_order(library)
        # expert index -> position in the escalation ladder (the inverse
        # permutation the fused cascade kernel consumes)
        self._ladder_pos = np.zeros(len(library), np.int64)
        for pos, e in enumerate(self._esc_order):
            self._ladder_pos[e] = pos
        # per-expert health/overload tracker (None = health-unaware
        # engine, the fallback stage is a strict no-op) and the bound on
        # route-time fallback re-selections per request
        self.health = health
        self.fallback_max_depth = fallback_max_depth
        # live ExpertScheduler while serve() runs (failure-injection
        # handle for tests/benchmarks); None outside serve()
        self.scheduler: ExpertScheduler | None = None
        self._now = now_fn
        self.queue: list[Request] = []
        self.stats = EngineStats()

        # online adaptation: replay buffer + jit'd incremental update.
        # The buffer fills whenever feedback is available (telemetry and
        # offline analysis want it even for a frozen router); updates
        # only happen when adapt_every > 0.
        if adapt_every < 0 or adapt_batch < 1:
            raise ValueError("adapt_every must be >= 0 and "
                             "adapt_batch >= 1")
        if adapt_every > 0 and replay_cap <= 0:
            raise ValueError("adapt_every > 0 needs a replay buffer "
                             "(replay_cap >= 1)")
        self.adapt_every = adapt_every
        self.adapt_batch = adapt_batch
        self.replay = ReplayBuffer(replay_cap) if replay_cap > 0 else None
        self._adapt_rng = np.random.default_rng(adapt_seed)
        self._fb_at_last_update = 0
        if adapt_every > 0:
            self._update_step = make_router_update_step(
                rc, lr=adapt_lr, ema=adapt_ema, trainable=adapt_trainable)

            def _adapt_step(p, t, e, o):
                # pre/post prediction error fused with the update into
                # one jit'd program: one device->host pull per adaptation
                # step instead of two blocking float() syncs (JXL001)
                pre = router_prediction_error(p, rc, t, e, o)
                new_p, _ = self._update_step(p, t, e, o)
                post = router_prediction_error(new_p, rc, t, e, o)
                return new_p, jnp.stack([pre, post])

            self._adapt_step = jax.jit(_adapt_step)

        # the staged pipeline: Route -> Cascade (admission half) and
        # Execute -> Feedback (flush half), composed over this engine's
        # jit'd primitives
        self.pipeline = ServingPipeline(self)

        self._cnames = [c.name for c in self.constraints]
        self._cmat = constraint_matrix(self.constraints, rc.n_models)

        # lazy sigma pass: only cascade-enabled requests pay for it, so
        # the min_confidence=0 path runs the exact pre-cascade jits
        self._sigma = jax.jit(
            lambda p, toks: predict_uncertainty(p, rc, {"tokens": toks}))

        # semantic-tier path: pooled embedding and head-from-embedding
        # jits, compiled only if the semantic cache tier is enabled (the
        # T3 probe needs the embedding before it knows whether a fresh
        # score is needed, so the score is split at the embedding)
        self._embed = jax.jit(
            lambda p, toks: router_embed(p, rc, {"tokens": toks}))
        self._head_from_emb = jax.jit(
            lambda p, emb: losses_from_emb(p["head"], emb))

        if use_kernel:
            cmat = self._cmat

            def _decide(p, toks, lam):
                emb = router_embed(p, rc, {"tokens": toks})
                return rs_ops.router_route(emb, p["head"], cmat, lam,
                                           interpret=interpret)

            self._decide = jax.jit(_decide)
            if fused_cascade:
                ladder = jnp.asarray(self._ladder_pos, jnp.int32)

                def _decide_cascade(p, toks, lam):
                    emb = router_embed(p, rc, {"tokens": toks})
                    return rc_ops.router_route_cascade(
                        emb, p["head"], p["unc"], cmat, lam, ladder,
                        interpret=interpret)

                self._decide_cascade = jax.jit(_decide_cascade)
        else:
            self._score = jax.jit(
                lambda p, toks: predict_losses(p, rc, {"tokens": toks},
                                               use_kernel=False))
        self._expert_fns = {}
        self._expert_idx = {}
        for i, e in enumerate(library.experts):
            self._expert_fns[e.name] = jax.jit(
                functools.partial(self._expert_forward, cfg=e.cfg))
            self._expert_idx[e.name] = i

        # ------------------------------------------------ mesh wiring
        # A (data, model) mesh makes the pipeline multi-device: the
        # routing stage shards admission batches over the "data" axis,
        # and the Execute stage places each expert on a "model"-axis
        # slice (serving.placement) so lane flushes land in per-device
        # streams that overlap instead of serializing on device 0.
        # mesh=None (the default) is the single-device engine,
        # bit-for-bit — none of the fields below are consulted.
        self.mesh = mesh
        self.placement: PlacementMap | None = None
        self.streams: StreamClock | None = None
        self._data_ext = 1
        self._mesh_rp_cache: tuple[int, object] | None = None
        if mesh is not None:
            missing = {"data", "model"} - set(mesh.axis_names)
            if missing:
                raise ValueError(f"serving mesh needs axes "
                                 f"('data', 'model'); missing {missing}")
            self._data_ext = int(mesh.shape["data"])
            model_ext = int(mesh.shape["model"])
            if placement is None:
                placement = plan_placement(
                    [e.n_params for e in library.experts], model_ext,
                    replicate_hot=replicate_hot)
            if placement.n_slices != model_ext:
                raise ValueError(f"placement has {placement.n_slices} "
                                 f"slices but the mesh's model axis is "
                                 f"{model_ext}")
            if placement.n_experts != len(library):
                raise ValueError("placement sized for a different library")
            self.placement = placement
            # device grid (data, model): slice k owns column k; stream
            # index == flat device index r * model_ext + k
            grid = np.asarray(mesh.devices).reshape(self._data_ext,
                                                    model_ext)
            self._devices = list(grid.reshape(-1))
            self.streams = StreamClock(len(self._devices))
            self._expert_streams = {
                i: [r * model_ext + k
                    for k in placement.slices_for(i)
                    for r in range(self._data_ext)]
                for i in range(len(library))}
            # per-(expert, stream) committed parameter replicas, filled
            # lazily on first dispatch so unused replicas cost nothing
            self._expert_params_on: dict[tuple[int, int], object] = {}
            if self._data_ext > 1:
                if use_kernel:
                    # GSPMD cannot partition pallas_call, so the fused
                    # decision runs under shard_map: per-device blocks
                    # of the batch through the same kernel, params
                    # replicated (P() spec)
                    from jax.sharding import PartitionSpec as P
                    cmat = self._cmat

                    def _decide_sharded(p, toks, lam):
                        emb = router_embed(p, rc, {"tokens": toks})
                        return rs_ops.router_route(emb, p["head"], cmat,
                                                   lam,
                                                   interpret=interpret)

                    self._decide_mesh = jax.jit(jax.shard_map(
                        _decide_sharded, mesh=mesh,
                        in_specs=(P(), P("data", None), P("data", None)),
                        out_specs=(P("data", None), P("data")),
                        check_vma=False))
                else:
                    # GSPMD path: same predict_losses program, traced
                    # under the activation-sharding context so
                    # shard_act pins the batch axis through the encoder
                    self._score_mesh = jax.jit(
                        lambda p, toks: predict_losses(
                            p, rc, {"tokens": toks}, use_kernel=False))

    def _mesh_router_params(self):
        """Router params replicated onto the serving mesh, re-put only
        when adaptation swaps the version (device transfer once per
        snapshot, not once per batch)."""
        if (self._mesh_rp_cache is None
                or self._mesh_rp_cache[0] != self.router_version):
            rp = jax.device_put(self.router_params,
                                replicated_sharding(self.mesh))
            self._mesh_rp_cache = (self.router_version, rp)
        return self._mesh_rp_cache[1]

    def mesh_summary(self) -> dict | None:
        """Placement + per-device stream telemetry (None without a
        mesh).  Deliberately *not* part of ``EngineStats`` — the
        1x1-mesh engine must stay bit-for-bit identical to the meshless
        engine, EngineStats included."""
        if self.mesh is None:
            return None
        names = [e.name for e in self.library.experts]
        # where the committed replicas actually live (filled by dispatch
        # or warm_mesh), read back from the arrays themselves
        replicas = defaultdict(set)
        for (ei, _), ep in self._expert_params_on.items():
            for leaf in jax.tree.leaves(ep):
                replicas[names[ei]].update(d.id for d in leaf.devices())
        return {
            "mesh": {k: int(v) for k, v in self.mesh.shape.items()},
            "placement": self.placement.summary(names),
            "replica_devices": {n: sorted(replicas[n]) for n in names
                                if n in replicas},
            "streams": self.streams.summary(),
        }

    def warm_mesh(self, seq_len: int,
                  bucket_sizes: Sequence[int] | None = None) -> int:
        """Pre-place every expert replica and pre-compile every
        (expert, replica device, bucket size) execution variant.

        Flush dispatch picks the least-busy replica stream at flush
        time, so which (expert, device) variants a warm *serving* pass
        touches depends on wall-clock timings — a later flush can land
        on a device whose program was never compiled and eat the
        compile inside measured traffic.  Serving drivers and
        ``bench_mesh`` call this once up front instead; it is a no-op
        (returns 0) without a mesh.  Streams are not charged — warming
        is not traffic."""
        if self.placement is None:
            return 0
        if bucket_sizes is None:
            bucket_sizes = [b for b in (1, 2, 4, 8, 16, 32, 64, 128)
                            if b <= self.lane_target] or [self.lane_target]
        compiled = 0
        for ei, streams in self._expert_streams.items():
            e = self.library[ei]
            fn = self._expert_fns[e.name]
            for slot in streams:
                dev = self._devices[slot]
                key = (ei, slot)
                ep = self._expert_params_on.get(key)
                if ep is None:
                    ep = jax.device_put(e.params, dev)
                    self._expert_params_on[key] = ep
                for b in bucket_sizes:
                    zi = np.zeros((b, seq_len), np.int32)
                    preds, _, _ = fn(ep, jax.device_put(zi, dev),
                                     jax.device_put(zi, dev),
                                     jax.device_put(zi, dev))
                    jax.block_until_ready(preds)
                    compiled += 1
        return compiled

    @property
    def router_params(self):
        """The live router snapshot's parameter tree (read-only view;
        adaptation publishes new trees via ``VersionedParams.swap``)."""
        return self._router.params

    @property
    def router_version(self) -> int:
        """Monotone version of the live router snapshot — part of every
        decision-cache key."""
        return self._router.version

    @staticmethod
    def _expert_forward(params, toks, targets, mask, *, cfg):
        """Per-example predictions, masked NLL and masked accuracy.

        Padded rows carry an all-zero mask, so their loss/accuracy reduce
        to 0 under the max(denominator, 1) guard and are dropped host-side.
        """
        logits, _, _ = forward(params, cfg, {"tokens": toks}, mode="train",
                               remat=False)
        logits = logits.astype(jnp.float32)
        preds = jnp.argmax(logits, axis=-1)
        # masked token NLL, one-hot contraction (see models.model.cross_entropy)
        logz = jax.nn.logsumexp(logits, axis=-1)
        onehot = jax.nn.one_hot(targets, logits.shape[-1], dtype=jnp.float32)
        gold = jnp.einsum("bsv,bsv->bs", logits, onehot)
        m = mask.astype(jnp.float32)
        denom = jnp.maximum(m.sum(-1), 1.0)
        ex_loss = ((logz - gold) * m).sum(-1) / denom
        ex_acc = ((preds == targets) * m).sum(-1) / denom
        return preds, ex_loss, ex_acc

    # ------------------------------------------------------------- api

    def submit(self, req: Request):
        if req.arrival is None:
            req.arrival = self._now()
        self.queue.append(req)

    def _bucket(self, n: int) -> int:
        return bucket_size(n) if self.buckets else n

    # ---------------------------------------------------- routing stage

    def _score_batch(self, reqs: list[Request]) -> tuple[np.ndarray,
                                                         np.ndarray]:
        """Score one batch with the router (no cache).

        Returns ``(pred_losses, choice)``: the router's predicted
        per-expert losses (B, M) f32 and the selected expert index (B,)
        int under each request's lambda-weighted constraints.
        """
        B = len(reqs)
        toks = np.stack([r.tokens for r in reqs])
        t0 = self._now()
        data_par = self._data_ext > 1
        if self.use_kernel:
            # fused path: constraint add + argmin happen on-device inside
            # router_score_fused; pad to a bucket so the jit'd decision
            # function compiles once per bucket, not per ragged tail.
            lam = lambda_matrix(reqs, self._cnames)
            Bp = self._bucket(B)
            if data_par and Bp % self._data_ext:
                # shard_map needs the batch divisible by the data axis
                Bp += self._data_ext - Bp % self._data_ext
            if Bp != B:
                toks = np.concatenate(
                    [toks, np.zeros((Bp - B,) + toks.shape[1:], toks.dtype)])
                lam = np.concatenate(
                    [lam, np.zeros((Bp - B, lam.shape[1]), lam.dtype)])
            if data_par:
                # data-parallel decision: batch rows sharded over the
                # mesh's "data" axis, params replicated, the same fused
                # kernel per device block (shard_map — see __init__)
                bs = batch_sharding(self.mesh, 2, toks.shape)
                pred, choice = self._decide_mesh(
                    self._mesh_router_params(),
                    jax.device_put(toks, bs),
                    jax.device_put(lam, batch_sharding(self.mesh, 2,
                                                       lam.shape)))
            else:
                pred, choice = self._decide(self.router_params,
                                            jnp.asarray(toks),
                                            jnp.asarray(lam))
            if Bp not in self.stats.router_tiles:
                # effective tile actually launched for this padded batch
                # (block_b silently clamps to the batch — see
                # kernels.router_score.kernel.launch_plan)
                self.stats.router_tiles[Bp] = {
                    **rs_ops.decision_plan(Bp), "launches": 0}
            self.stats.router_tiles[Bp]["launches"] += 1
            if sanitize.sanitize_enabled():
                self._sanitize_batch(toks, pred, choice)
            pred = np.asarray(pred)[:B]
            choice = np.asarray(choice)[:B]
        else:
            if data_par:
                Bp = B
                if Bp % self._data_ext:
                    Bp += self._data_ext - Bp % self._data_ext
                    toks = np.concatenate(
                        [toks,
                         np.zeros((Bp - B,) + toks.shape[1:], toks.dtype)])
                # GSPMD data-parallel scoring: inputs NamedSharding'd by
                # batch (sharding/rules.py "batch" -> "data"), traced
                # under the activation-sharding context so the encoder
                # keeps the batch axis sharded end to end
                tsh = jax.device_put(toks,
                                     batch_sharding(self.mesh, 2,
                                                    toks.shape))
                with activation_sharding(self.mesh, DEFAULT_RULES):
                    pred_dev = self._score_mesh(self._mesh_router_params(),
                                                tsh)
                if sanitize.sanitize_enabled():
                    self._sanitize_batch(toks, pred_dev)
                pred = np.asarray(pred_dev)[:B]
            else:
                pred_dev = self._score(self.router_params,
                                       jnp.asarray(toks))
                if sanitize.sanitize_enabled():
                    self._sanitize_batch(toks, pred_dev)
                pred = np.asarray(pred_dev)
            # score = L-hat + sum_j lambda_j C_j, argmin on the host
            scores = pred.copy()
            for c in self.constraints:
                lam = np.array([r.lambdas.get(c.name, 0.0) for r in reqs])
                scores = scores + lam[:, None] * c.values[None, :]
            choice = scores.argmin(axis=1)
        self.stats.router_time_s += self._now() - t0
        self.stats.router_batches += 1
        return pred, choice

    def _use_fused_cascade(self, reqs: list[Request]) -> bool:
        """Whether this batch takes the one-launch cascade decision:
        the flag is on, the kernel path is active, the router carries an
        uncertainty head, the cascade is enabled, the engine is not
        data-sharded (shard_map wiring covers the plain kernel only),
        and the batch actually contains cascade traffic.  Batches that
        fail any gate run the staged path bit-for-bit."""
        return (self.fused_cascade and self.use_kernel
                and self.cascade_max_depth > 0
                and self._data_ext == 1
                and "unc" in self.router_params
                and any(r.min_confidence > 0.0 for r in reqs))

    def _score_cascade_batch(self, reqs: list[Request]) -> tuple[
            np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """One-launch cascade scoring: predicted losses, per-expert
        sigma, constrained first pick and the router-preferred depth-1
        escalation target, all from a single fused kernel launch
        (``kernels.router_cascade``).  Mirrors ``_score_batch``'s
        bucket padding and telemetry."""
        B = len(reqs)
        toks = np.stack([r.tokens for r in reqs])
        lam = lambda_matrix(reqs, self._cnames)
        t0 = self._now()
        Bp = self._bucket(B)
        if Bp != B:
            toks = np.concatenate(
                [toks, np.zeros((Bp - B,) + toks.shape[1:], toks.dtype)])
            lam = np.concatenate(
                [lam, np.zeros((Bp - B, lam.shape[1]), lam.dtype)])
        pred, sigma, choice, esc = self._decide_cascade(
            self.router_params, jnp.asarray(toks), jnp.asarray(lam))
        if Bp not in self.stats.router_tiles:
            self.stats.router_tiles[Bp] = {**rc_ops.decision_plan(Bp),
                                           "launches": 0}
        self.stats.router_tiles[Bp]["launches"] += 1
        if sanitize.sanitize_enabled():
            self._sanitize_batch(toks, pred, choice)
        pred = np.asarray(pred)[:B]
        sigma = np.asarray(sigma)[:B]
        choice = np.asarray(choice)[:B]
        esc = np.asarray(esc)[:B]
        self.stats.router_time_s += self._now() - t0
        self.stats.router_batches += 1
        return pred, choice, sigma, esc

    def _sanitize_batch(self, toks, pred, choice=None):
        """``REPRO_SANITIZE``: validate one scored batch.  Token ids are
        range-checked host-side (they arrive as numpy); router outputs
        are checked under checkify (see ``kernels.sanitize`` for why the
        checks wrap the jit boundary instead of the kernel)."""
        vocab = self.rc.vocab_size
        if toks.min() < 0 or toks.max() >= vocab:
            raise ValueError(
                f"router_score: token id out of range [0, {vocab})")
        M = self.rc.n_models

        def _checks(p, c):
            sanitize.check_finite("router_score", "predicted losses", p)
            if c is not None:
                sanitize.check_in_range("router_score", "expert choice",
                                        c, 0, M)

        if choice is None:
            sanitize.run_checks(lambda p: _checks(p, None), pred)
        else:
            sanitize.run_checks(_checks, pred, choice)

    def _embed_batch(self, reqs: list[Request]) -> np.ndarray:
        """Pooled router embeddings (B, d) for the semantic cache tier —
        one encoder pass over the batch, bucket-padded like
        ``_score_batch``.  Counts as a router forward in the stats (it
        is most of one)."""
        B = len(reqs)
        toks = np.stack([r.tokens for r in reqs])
        t0 = self._now()
        Bp = self._bucket(B)
        if Bp != B:
            toks = np.concatenate(
                [toks, np.zeros((Bp - B,) + toks.shape[1:], toks.dtype)])
        emb = np.asarray(self._embed(self.router_params,
                                     jnp.asarray(toks)))[:B]
        self.stats.router_time_s += self._now() - t0
        self.stats.router_batches += 1
        return emb

    def _score_from_emb(self, reqs: list[Request], emb: np.ndarray,
                        ) -> tuple[np.ndarray, np.ndarray]:
        """Finish scoring from precomputed pooled embeddings: loss head
        + host-side constrained argmin (the reference-path math — the
        semantic tier reuses the T3 probe's encoder pass instead of
        re-running the fused decision kernel)."""
        B = len(reqs)
        t0 = self._now()
        Bp = self._bucket(B)
        embp = emb
        if Bp != B:
            embp = np.concatenate(
                [emb, np.zeros((Bp - B, emb.shape[1]), emb.dtype)])
        pred = np.asarray(self._head_from_emb(self.router_params,
                                              jnp.asarray(embp)))[:B]
        scores = pred.copy()
        for c in self.constraints:
            lam = np.array([r.lambdas.get(c.name, 0.0) for r in reqs])
            scores = scores + lam[:, None] * c.values[None, :]
        choice = scores.argmin(axis=1)
        self.stats.router_time_s += self._now() - t0
        return pred, choice

    def _sigma_batch(self, reqs: list[Request]) -> np.ndarray:
        """Per-expert predictive uncertainty sigma (B, M) for a batch —
        a second (tiny) router pass, paid only by cascade traffic.

        Deliberately NOT fused with the scoring jit: reusing its
        embedding would change the compiled program and forfeit the
        bit-for-bit single-shot parity with the pre-cascade engine that
        tests/test_cascade.py enforces.  The router is BERT-tiny scale,
        so the duplicate encoder pass is noise next to expert
        execution; revisit only if the router grows."""
        B = len(reqs)
        toks = np.stack([r.tokens for r in reqs])
        Bp = self._bucket(B)
        if Bp != B:
            toks = np.concatenate(
                [toks, np.zeros((Bp - B,) + toks.shape[1:], toks.dtype)])
        return np.asarray(
            self._sigma(self.router_params, jnp.asarray(toks)))[:B]

    def _cascade(self, reqs: list[Request], pred: np.ndarray,
                 choice: np.ndarray) -> tuple[np.ndarray, np.ndarray,
                                              np.ndarray]:
        """Abstention/escalation pass over a scored batch.

        Returns ``(final_choice (B,), depth (B,), confidence (B,))``.
        When no request in the batch asks for a confidence floor the
        sigma pass is skipped and the scores' choice passes through
        untouched — the single-shot fast path.  Escalation is router-
        preferred: each step re-runs the constrained objective over the
        strictly-larger experts (``cascade_choice`` with the request's
        lambda-weighted scores).
        """
        B = len(reqs)
        depth = np.zeros(B, np.int64)
        conf = np.ones(B, np.float64)
        if (self.cascade_max_depth <= 0
                or not any(r.min_confidence > 0.0 for r in reqs)):
            return choice, depth, conf
        confm = confidence_scores(self._sigma_batch(reqs))
        # constrained routing scores L-hat + sum_j lambda_j C_j, (B, M)
        scores = pred + lambda_matrix(reqs, self._cnames) @ self._cmat
        final = np.array(choice, np.int64, copy=True)
        for i, r in enumerate(reqs):
            if r.min_confidence <= 0.0:
                continue
            final[i], depth[i] = cascade_choice(
                int(choice[i]), confm[i], r.min_confidence,
                self._esc_order, self.cascade_max_depth, scores[i])
            conf[i] = confm[i, final[i]]
        return final, depth, conf

    def _cascade_fused(self, reqs: list[Request], pred: np.ndarray,
                       choice: np.ndarray, sigma: np.ndarray,
                       esc: np.ndarray) -> tuple[np.ndarray, np.ndarray,
                                                 np.ndarray]:
        """Epilogue of the one-launch cascade decision: resolve each
        request's per-request threshold against the kernel's confidence
        and depth-1 escalation target.

        Same contract as ``_cascade`` — ``(final, depth, confidence)``
        with confidence computed in float64 from sigma exactly as the
        staged path does.  The depth-1 common case needs no further
        scoring work; the rare request that is *still* under-confident
        after one step (and has ladder left, and ``cascade_max_depth >
        1``) re-runs the staged ``cascade_choice`` walk from scratch,
        so deep escalations match the staged path by construction."""
        B = len(reqs)
        depth = np.zeros(B, np.int64)
        conf = np.ones(B, np.float64)
        final = np.array(choice, np.int64, copy=True)
        confm = confidence_scores(sigma)
        top = len(self._esc_order) - 1
        scores = None
        for i, r in enumerate(reqs):
            thr = r.min_confidence
            if thr <= 0.0:
                continue
            c0 = int(choice[i])
            if confm[i, c0] >= thr or self._ladder_pos[c0] >= top:
                conf[i] = confm[i, c0]
                continue
            e1 = int(esc[i])
            if (confm[i, e1] < thr and self._ladder_pos[e1] < top
                    and self.cascade_max_depth > 1):
                # depth >= 2: staged walk from scratch (exact fallback)
                if scores is None:
                    scores = (pred
                              + lambda_matrix(reqs, self._cnames)
                              @ self._cmat)
                final[i], depth[i] = cascade_choice(
                    c0, confm[i], thr, self._esc_order,
                    self.cascade_max_depth, scores[i])
                conf[i] = confm[i, final[i]]
            else:
                final[i], depth[i], conf[i] = e1, 1, confm[i, e1]
        return final, depth, conf

    def _route_admitted(self, reqs: list[Request]) -> tuple[
            np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray,
            np.ndarray]:
        """Run the admission half of the pipeline (Route -> Cascade ->
        Fallback): cached requests skip scoring, misses are scored as
        one (smaller) batch, cascaded, and memoised post-cascade; the
        health consult then re-routes any row whose chosen expert is
        down or saturated (no-op without a health tracker).

        Returns ``(pred_losses (B, M), choice (B,), cached (B,) bool,
        depth (B,) int, confidence (B,) float, fallback_depth (B,)
        int)`` — ``choice`` is the final post-escalation, post-fallback
        expert.
        """
        ctx = self.pipeline.admit(reqs)
        return (ctx.pred, ctx.choice, ctx.cached, ctx.depth,
                ctx.confidence, ctx.fallback_depth)

    def _route_batch(self, reqs: list[Request]) -> tuple[np.ndarray,
                                                         np.ndarray]:
        """Route one batch of requests (cache-aware); see
        ``_route_admitted`` for the variant that also reports hits,
        cascade depth and confidence."""
        pred, choice, _, _, _, _ = self._route_admitted(reqs)
        return pred, choice

    # ------------------------------------------------ online adaptation

    def _maybe_adapt(self):
        """Feedback-cadenced router refresh (called by the Feedback
        stage after each flush).

        One incremental update per ``adapt_every`` published feedback
        samples — a large flush that publishes several multiples of
        ``adapt_every`` at once applies every update it owes, so the
        adaptation rate tracks the documented cadence regardless of
        micro-batch size.  Each update replays a fresh batch, runs the
        jit'd step on shadow weights, measures the batch prediction
        error before/after, and publishes the new snapshot with an
        atomic version-bumping swap.  The decision cache is cleared on
        swap — the version in the key already makes stale verdicts
        unreachable; clearing just reclaims their memory.
        """
        if self.adapt_every <= 0 or self.replay is None:
            return
        while (self.replay.seen - self._fb_at_last_update
               >= self.adapt_every):
            self._fb_at_last_update += self.adapt_every
            t0 = self._now()
            toks, eidx, obs = self.replay.sample(self.adapt_batch,
                                                 self._adapt_rng)
            jt, je, jo = (jnp.asarray(toks), jnp.asarray(eidx),
                          jnp.asarray(obs))
            new_params, errs = self._adapt_step(self.router_params,
                                                jt, je, jo)
            errs = np.asarray(errs)  # one sync for both error scalars
            self._router = self._router.swap(new_params)
            if self.cache is not None:
                self.cache.clear()
            self._assert_cache_version()
            self.stats.adapt_updates += 1
            self.stats.router_version = self._router.version
            self.stats.adapt_pre_err = float(errs[0])
            self.stats.adapt_post_err = float(errs[1])
            self.stats.adapt_time_s += self._now() - t0

    def _assert_cache_version(self):
        """Sanitizer invariant, checked after every swap: no surviving
        decision-cache entry may carry a router version other than the
        live snapshot's — a stale hit would serve verdicts scored by
        superseded parameters."""
        if self.cache is None:
            return
        stale = self.cache.stale_versions(self._router.version)
        assert not stale, (
            f"decision cache holds entries for router version(s) "
            f"{sorted(stale)} but version {self._router.version} is live")

    # --------------------------------------------------- expert executor

    def _run_expert(self, e, reqs: list[Request]):
        """Execute one padded per-expert micro-batch; returns per-example
        (preds, loss, acc) arrays trimmed back to len(reqs).

        With a placement map (mesh serving), the micro-batch is
        *dispatched*: the least-busy device stream among the expert's
        replica slices runs the whole batch with parameters committed to
        that device (first dispatch per (expert, device) pays the
        transfer, after that the replica is resident).  Committed
        execution keeps the per-flush program identical to the
        single-device engine — the mesh changes *where* a flush runs,
        never *what* it computes."""
        n = len(reqs)
        Bp = self._bucket(n)
        S = len(reqs[0].tokens)
        toks = np.zeros((Bp, S), reqs[0].tokens.dtype)
        targets = np.zeros((Bp, S), np.int32)
        mask = np.zeros((Bp, S), np.int32)
        for j, r in enumerate(reqs):
            toks[j] = r.tokens
            if r.targets is not None:
                targets[j] = r.targets
            if r.mask is not None:
                mask[j] = r.mask
        if self.placement is not None:
            ei = self._expert_idx[e.name]
            slot = self.streams.least_busy(self._expert_streams[ei])
            dev = self._devices[slot]
            key = (ei, slot)
            ep = self._expert_params_on.get(key)
            if ep is None:
                ep = jax.device_put(e.params, dev)
                self._expert_params_on[key] = ep
            t0 = self._now()
            preds, ex_loss, ex_acc = self._expert_fns[e.name](
                ep, jax.device_put(toks, dev),
                jax.device_put(targets, dev), jax.device_put(mask, dev))
            out = (np.asarray(preds)[:n], np.asarray(ex_loss)[:n],
                   np.asarray(ex_acc)[:n])
            # attribute the flush's (blocked) wall time to its stream —
            # the overlapped-makespan signal bench_mesh scales on
            self.streams.record(slot, self._now() - t0, tokens=n * S)
            self.stats.bucket_hits[Bp] += 1
            self.stats.padded_rows += Bp - n
            return out
        preds, ex_loss, ex_acc = self._expert_fns[e.name](
            e.params, jnp.asarray(toks), jnp.asarray(targets),
            jnp.asarray(mask))
        self.stats.bucket_hits[Bp] += 1
        self.stats.padded_rows += Bp - n
        return (np.asarray(preds)[:n], np.asarray(ex_loss)[:n],
                np.asarray(ex_acc)[:n])

    def _execute(self, expert_idx: int, entries: list[LaneEntry],
                 reason: str) -> list[Result]:
        """Run the flush half of the pipeline (Execute -> Feedback) on
        one per-expert micro-batch and return its Results."""
        return self.pipeline.flush(expert_idx, entries, reason)

    def _flush_or_fail(self, sched: ExpertScheduler, expert_idx: int,
                       entries: list[LaneEntry], reason: str,
                       ) -> list[Result]:
        """Execute one scheduled flush, honouring the scheduler's
        armed failure injections and feeding the health tracker.

        A failed flush never loses a request: with a health tracker and
        fallback budget left, its entries are re-routed through the
        fallback chain into other experts' lanes (``Result`` arrives
        later, with a higher ``fallback_depth``); otherwise each entry
        yields a terminal failed ``Result`` (``failed=True``,
        ``flush_reason="failed"``) so the client sees the rejection
        instead of a hang."""
        if sched.take_failure(expert_idx):
            if self.streams is not None:
                # a failed flush occupies no stream time, but the
                # per-device telemetry should still show where it was
                # headed: charge the failure to the home slice's
                # least-busy stream (the dispatch _run_expert would
                # have made)
                self.streams.record_failure(self.streams.least_busy(
                    self._expert_streams[expert_idx]))
            return self._failed_flush(sched, expert_idx, entries)
        t0 = self._now()
        out = self._execute(expert_idx, entries, reason)
        if self.health is not None:
            self.health.observe_flush(expert_idx, self._now() - t0,
                                      ok=True)
        return out

    def _unrecord_result(self, res: Result) -> None:
        """Reverse the per-request ``EngineStats`` accounting of one
        Result whose speculative execution was discarded (the cascade
        verdict escalated after the provisional entry already flushed).

        Only the per-request counters are reverted — flush counts,
        bucket hits, padded rows and expert wall time stay, because the
        compute really happened; ``spec_wasted_tokens`` is the honest
        record of that waste.  Replay feedback from the wasted
        execution also stays: the (prompt, expert, loss) observation is
        real even though the Result is withdrawn."""
        st = self.stats
        if res.failed:
            st.failed -= 1
            return
        st.served -= 1
        st.per_expert[res.expert] -= 1
        if st.per_expert[res.expert] == 0:
            del st.per_expert[res.expert]
        st.total_flops -= res.flops_proxy
        try:
            st.latencies.remove(res.latency_s)
        except ValueError:
            pass
        st.cascade_depth_hist[res.cascade_depth] -= 1
        if st.cascade_depth_hist[res.cascade_depth] == 0:
            del st.cascade_depth_hist[res.cascade_depth]
        try:
            st.tier_latencies[res.cascade_depth].remove(res.latency_s)
        except (KeyError, ValueError):
            pass
        if res.cascade_depth > 0:
            st.escalations -= 1

    def _failed_flush(self, sched: ExpertScheduler, expert_idx: int,
                      entries: list[LaneEntry]) -> list[Result]:
        """One lane flush failed: record it, then re-route or fail each
        entry.  Re-routing re-scores the request's own constrained
        objective with the failed expert masked out (same rule as the
        route-time fallback stage) and re-enqueues it; its
        ``fallback_depth`` stays monotone across the bounces, and a
        request whose depth would exceed ``fallback_max_depth`` plus one
        full sweep of the library fails terminally instead of bouncing
        forever."""
        e = self.library[expert_idx]
        self.stats.expert_failures[e.name] += 1
        if self.health is not None:
            self.health.record_failure(expert_idx)
        budget = self.fallback_max_depth + len(self.library)
        failed: list[Result] = []
        lam = lambda_matrix([en.req for en in entries], self._cnames)
        scores = None
        if self.health is not None and self.fallback_max_depth > 0:
            scores = np.stack([en.pred for en in entries]) + lam @ self._cmat
            healthy = self.health.healthy_mask().copy()
            avail = self.health.available_mask().copy()
            # the expert that just failed is off the table either way
            healthy[expert_idx] = avail[expert_idx] = False
        now = self._now()
        for j, en in enumerate(entries):
            target = None
            if scores is not None and en.fallback_depth < budget:
                final, fdepth, degraded = fallback_choice(
                    scores[j], healthy, avail, expert_idx,
                    self._esc_order, self.fallback_max_depth)
                if final != expert_idx:
                    target = (final, fdepth, degraded)
            if target is None:
                r = en.req
                self.stats.failed += 1
                failed.append(Result(
                    uid=r.uid, expert=e.name, pred_losses=en.pred,
                    predictions=np.zeros(0, np.int64), loss=None,
                    accuracy=None, flops_proxy=0.0,
                    latency_s=(max(now - r.arrival, 0.0)
                               if r.arrival is not None else 0.0),
                    cached=en.cached, flush_reason="failed",
                    cascade_depth=en.depth, confidence=en.confidence,
                    fallback_depth=en.fallback_depth, failed=True))
                continue
            final, fdepth, degraded = target
            self.stats.reroutes += 1
            if degraded:
                self.stats.degraded += 1
            sched.push(final, en.req, en.pred, en.cached, en.depth,
                       en.confidence, en.fallback_depth + fdepth)
        return failed

    # -------------------------------------------------------- disciplines

    def run(self) -> list[Result]:
        """FIFO drain: route the queue in admission-batch slices and
        launch every per-expert group immediately, however ragged.

        Returns one Result per request.  This is the baseline discipline
        ``serve()`` is benchmarked against (``bench_scheduler``).
        """
        results: list[Result] = []
        while self.queue:
            batch, self.queue = (self.queue[:self.max_batch],
                                 self.queue[self.max_batch:])
            (pred, choice, cached, depth, conf,
             fdepth) = self._route_admitted(batch)
            by_expert: dict[int, list[int]] = defaultdict(list)
            for i, c in enumerate(choice):
                by_expert[int(c)].append(i)
            for mi, idxs in sorted(by_expert.items()):
                entries = [LaneEntry(batch[i], pred[i], i, bool(cached[i]),
                                     int(depth[i]), float(conf[i]),
                                     int(fdepth[i]))
                           for i in idxs]
                results.extend(self._execute(mi, entries, "fifo"))
        return results

    def serve(self, request_iter: Iterable[Request | None],
              ) -> Iterator[Result]:
        """Continuous batching: stream requests in, stream Results out.

        ``request_iter`` yields ``Request``s, or ``None`` as an *idle
        tick* (e.g. from an arrival simulator between arrivals) that
        gives the scheduler a chance to fire ``max_wait_s`` deadline
        flushes while no new work is arriving.  Admitted requests are
        scored in batches of up to ``max_batch`` and pushed into
        per-expert lanes; lanes flush on a full bucket or on deadline,
        and everything still pending is drained when the iterator is
        exhausted — shutdown leaves no request behind.  Requests already
        enqueued via ``submit()`` are admitted first.

        On an idle tick a partial admission batch is scored only once
        its oldest request has aged past ``max_wait_s / 2`` — bursts
        keep coalescing into batched router passes instead of
        degenerating to batch-of-1 scoring, while the lane deadline
        (measured from ``Request.arrival``) still bounds total wait.

        With ``speculate=True`` (and a cascade enabled, no health
        tracker) admission is split: every request is laned on its
        *router* choice immediately and the cascade verdict is deferred
        until after the tick's flushes launch.  A verdict that confirms
        the pick promotes the provisional entry in place; one that
        escalates cancels it (or, if it already flushed, discards the
        speculative Result and reverts its accounting) and re-lanes the
        request on the escalation target.  Exactly one Result per
        request either way; ``EngineStats`` counts hits, cancels and
        wasted work.
        """
        sched = ExpertScheduler(len(self.library), self.lane_target,
                                self.max_wait_s)
        if self.placement is not None:
            # each expert lane carries its home device slice so flushes
            # stream into the placement's per-device execution slots
            sched.assign_slots(self.placement)
        self.scheduler = sched
        admitted: list[Request] = []
        # speculation is sound only when the Fallback stage is a strict
        # no-op (no health tracker): deferring Cascade must not reorder
        # it around a health consult
        spec_on = (self.speculate and self.cascade_max_depth > 0
                   and self.health is None)
        # speculative-escalation state: admission contexts whose cascade
        # verdict is still deferred, the uids whose lane entries are
        # provisional, and Results from flushes that executed a
        # provisional entry before its verdict landed
        inflight: list[tuple[RouteContext, list[int]]] = []
        pending: dict = {}    # uid -> speculatively chosen expert
        held: dict = {}       # uid -> Result awaiting its verdict

        def _push_ctx(ctx, specs=frozenset()):
            for i, r in enumerate(ctx.reqs):
                sched.push(int(ctx.choice[i]), r, ctx.pred[i],
                           bool(ctx.cached[i]), int(ctx.depth[i]),
                           float(ctx.confidence[i]),
                           int(ctx.fallback_depth[i]), spec=i in specs)

        def _admit():
            reqs = list(admitted)
            admitted.clear()
            if spec_on:
                # lane everything on the router's first pick now; the
                # sigma/escalation verdict lands via _resolve() after
                # this tick's flushes have launched
                ctx = self.pipeline.route(RouteContext(reqs))
                spec_rows = [i for i in ctx.miss_idx
                             if reqs[i].min_confidence > 0.0]
                if spec_rows:
                    for i in spec_rows:
                        pending[reqs[i].uid] = int(ctx.choice[i])
                        self.stats.spec_launched += 1
                    _push_ctx(ctx, frozenset(spec_rows))
                    inflight.append((ctx, spec_rows))
                else:
                    # no escalation candidates in flight: finish the
                    # admission synchronously, identical to the
                    # non-speculative flow
                    self.pipeline.fallback(self.pipeline.cascade(ctx))
                    _push_ctx(ctx)
            else:
                (pred, choice, cached, depth, conf,
                 fdepth) = self._route_admitted(reqs)
                for i, r in enumerate(reqs):
                    sched.push(int(choice[i]), r, pred[i],
                               bool(cached[i]), int(depth[i]),
                               float(conf[i]), int(fdepth[i]))
            if self.health is not None:
                # saturation signal: every expert's pending depth folds
                # into its health EWMA at each admission (zeros included
                # so idle lanes decay)
                for mi, d in enumerate(sched.depths()):
                    self.health.observe_lane_depth(mi, d)

        def _resolve():
            # land every deferred verdict: finish Cascade -> Fallback
            # on the route-only contexts, then reconcile each
            # provisional lane entry — exactly one Result per request
            while inflight:
                ctx, spec_rows = inflight.pop(0)
                self.pipeline.fallback(self.pipeline.cascade(ctx))
                for i in spec_rows:
                    r = ctx.reqs[i]
                    first = pending.pop(r.uid)
                    final = int(ctx.choice[i])
                    d = int(ctx.depth[i])
                    cf = float(ctx.confidence[i])
                    if d == 0:
                        # hit: the provisional entry (or its already-
                        # flushed Result) becomes authoritative
                        self.stats.spec_hits += 1
                        en = sched.find_entry(first, r.uid)
                        if en is not None:
                            en.spec = False
                            en.confidence = cf
                        else:
                            res = held.pop(r.uid)
                            res.confidence = cf
                            yield res
                        continue
                    en = sched.remove_entry(first, r.uid)
                    if en is not None:
                        # still queued: cancel and re-lane on the
                        # escalation target — no wasted compute
                        self.stats.spec_cancelled += 1
                        sched.push(final, r, en.pred, en.cached, d, cf,
                                   en.fallback_depth)
                    else:
                        # the provisional copy already executed: count
                        # the waste, revert its per-request accounting,
                        # re-lane on the verdict's expert
                        self.stats.spec_wasted += 1
                        self.stats.spec_wasted_tokens += len(r.tokens)
                        self._unrecord_result(held.pop(r.uid))
                        sched.push(final, r, ctx.pred[i],
                                   bool(ctx.cached[i]), d, cf,
                                   int(ctx.fallback_depth[i]))

        if self.queue:
            queued, self.queue = self.queue, []
            request_iter = itertools.chain(queued, request_iter)

        for item in request_iter:
            if item is not None:
                if item.arrival is None:
                    item.arrival = self._now()
                admitted.append(item)
            # full batch admits immediately; a partial batch admits once
            # its oldest request has aged, whether the wake-up was a new
            # request or an idle tick — score it so its requests start
            # aging in their lanes
            if admitted and (len(admitted) >= self.max_batch
                             or (self._now() - admitted[0].arrival
                                 >= 0.5 * self.max_wait_s)):
                _admit()
            for mi, entries, reason in sched.pop_ready(self._now()):
                for res in self._flush_or_fail(sched, mi, entries,
                                               reason):
                    if res.uid in pending:
                        held[res.uid] = res
                    else:
                        yield res
            if inflight:
                yield from _resolve()
        # input exhausted: shutdown drain leaves no request behind
        if admitted:
            _admit()
        if inflight:
            yield from _resolve()
        # a drain flush may re-route entries into other lanes (failure
        # injection during shutdown), so drain until quiescent
        while sched.pending:
            for mi, entries, reason in sched.drain():
                yield from self._flush_or_fail(sched, mi, entries,
                                               reason)
        assert not inflight and not pending and not held, (
            "speculation left unresolved verdicts or held Results")
        for mi, peak in sched.peaks().items():
            name = self.library[mi].name
            self.stats.lane_peaks[name] = max(
                self.stats.lane_peaks.get(name, 0), peak)
        for mi, peak in sched.esc_peaks().items():
            name = self.library[mi].name + "@esc"
            self.stats.lane_peaks[name] = max(
                self.stats.lane_peaks.get(name, 0), peak)
