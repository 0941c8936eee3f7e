from repro.serving.cache import DecisionCache, DecisionCacheStack
from repro.serving.engine import TryageEngine, EngineStats, bucket_size
from repro.serving.feedback import ReplayBuffer
from repro.serving.frontend import AdmissionQueue, ServingFrontend, Session
from repro.serving.health import ExpertHealth, ExpertState
from repro.serving.kvstore import (DiskKVStore, KVStore, MemoryKVStore,
                                   SimulatedCrash)
from repro.serving.metrics import (MetricSpec, MetricsServer, metric_names,
                                   render, start_metrics_server)
from repro.serving import tracing
from repro.serving.pipeline import (CascadeStage, ExecuteStage,
                                    FallbackStage, FeedbackStage,
                                    FlushContext, RouteContext, RouteStage,
                                    ServingPipeline)
from repro.serving.requests import (Request, Result, lambda_matrix,
                                    parse_flags)
from repro.serving.scheduler import ExpertScheduler, Lane, LaneEntry
from repro.serving.semcache import (ExactNNIndex, SemanticCache,
                                    calibrate_eps)

__all__ = ["TryageEngine", "EngineStats", "Request", "Result",
           "bucket_size", "lambda_matrix", "parse_flags", "DecisionCache", "DecisionCacheStack",
           "KVStore", "MemoryKVStore", "DiskKVStore", "SimulatedCrash",
           "SemanticCache", "ExactNNIndex", "calibrate_eps",
           "ExpertScheduler", "Lane", "LaneEntry",
           "ReplayBuffer", "ServingPipeline", "RouteContext",
           "FlushContext", "RouteStage", "CascadeStage", "ExecuteStage",
           "FeedbackStage", "FallbackStage",
           "ExpertHealth", "ExpertState",
           "ServingFrontend", "Session", "AdmissionQueue",
           "MetricSpec", "MetricsServer", "metric_names", "render",
           "start_metrics_server", "tracing"]
