"""Serving (L5): the production inference subsystem.

Supersedes the old ``parallel/inference.py`` batched-queue toy (which
remains as a thin back-compat shim over this engine).  Pieces:

  batcher.py   deadline-aware dynamic micro-batching, pow2 shape
               buckets, admission control (block/shed)
  registry.py  versioned model registry, alias pinning ("prod" -> v7),
               hot-swap that drains in-flight batches, rollback = alias
               move, canary promotion with auto-rollback
               (set_alias(..., canary=frac)); loads serializer
               FORMAT_VERSION 1-4 checkpoints
  engine.py    N engine replicas over jax.local_devices(), round-robin
               dispatch with per-replica in-flight caps, AOT warmup of
               every (bucket, dtype) pair at load; replica supervision
               (crash/hang detect → retry elsewhere → respawn+re-warm,
               per-replica circuit breaker), poison-input bisection,
               typed request errors — every future always resolves
  metrics.py   fixed-bucket latency histograms + counters (incl. retry/
               respawn/circuit/canary/poison), exported on ui/server.py's
               /metrics endpoint (health on /healthz)
  decode.py    autoregressive decode engine for the transformer LM:
               paged KV-cache (ops/kv_cache.py), bucketed prefill/decode
               split, iteration-level continuous batching, seeded
               deterministic sampling, per-request stop conditions,
               crash-retry/poison-isolation/hot-swap decode-shaped;
               TTFT + time-per-output-token first-class (DecodeMetrics);
               prefill/decode disaggregation (role=..., PrefillHandoff
               KV-page transfer) + tensor-parallel sharded decode
  warmcache.py zero-cold-start: process-wide JAX persistent compile
               cache (JAX_COMPILATION_CACHE_DIR, else .cache/) +
               warmup bundles (serialized AOT executables next to the
               checkpoint zip; silent fallback to compile on any miss)
  autoscale.py load-driven replica autoscaling controller (hysteresis +
               cooldown + bounds, injectable clock); actuated by the
               engine supervisor loops via PR-7 birth/retire machinery
  tenancy.py   multi-tenant admission: TenantConfig SLO classes
               (slo_ms, fair-share weight, qps/concurrent quotas,
               shed/block policy), TenantTable atomic check-and-charge,
               typed TenantOverloadedError carrying the tenant — the
               batchers' per-tenant weighted-fair lanes read this table
  placement.py traffic-driven (model, host) placement over one fleet:
               per-model EWMA demand + the autoscale control law widen
               hot models, narrow/evict cold ones (warm-bundle loads),
               and demand-reload on a router model miss
  lifecycle.py the production flywheel: PromotionPipeline runs
               TRAIN → EVAL → REGISTER → CANARY → ROLL repeatedly with
               lineage-provenance registration, warm-bundle-at-save,
               bounded retries/deadlines, a crash-resumable journal,
               and lineage-aware regression rollback (docs/LIFECYCLE.md)

Reference lineage: DL4J's ParallelInference BATCHED mode + the model-
server role; design cf. the serving sections of "TensorFlow: A system
for large-scale machine learning" and TPU serving practice (PAPERS.md).
See docs/SERVING.md.
"""

from .autoscale import ReplicaAutoscaler
from .batcher import (
    ADMISSION_POLICIES, ContinuousBatcher, DeadlineExceededError,
    DynamicBatcher, OverloadedError, pow2_buckets,
)
from .decode import DecodeEngine, GenerationResult, PrefillHandoff
from .engine import (
    Engine, ModelNotLoadedError, PoisonInputError, ReplicaCrashError,
    ReplicaHungError, ServingUnavailableError,
)
from .fleet import FleetHost, FleetRouter, FleetTimeoutError, HttpHost
from .placement import PlacementController
from .tenancy import TenantConfig, TenantOverloadedError, TenantTable
from .lifecycle import (
    EvalGate, PipelineJournal, PipelineStageError, PromotionPipeline,
    StageDeadlineError, data_fingerprint, weights_sha,
)
from .metrics import (DecodeMetrics, FleetMetrics, LatencyHistogram,
                      ServingMetrics)
from .registry import CanaryRejectedError, ModelRegistry
from .warmcache import (
    bundle_path_for, device_fingerprint, enable_compile_cache, load_bundle,
    save_bundle,
)

__all__ = [
    "ADMISSION_POLICIES", "CanaryRejectedError", "ContinuousBatcher",
    "DeadlineExceededError",
    "DecodeEngine", "DecodeMetrics", "DynamicBatcher", "Engine",
    "EvalGate",
    "FleetHost", "FleetMetrics", "FleetRouter", "FleetTimeoutError",
    "GenerationResult", "HttpHost", "LatencyHistogram",
    "ModelNotLoadedError", "ModelRegistry",
    "OverloadedError", "PipelineJournal", "PipelineStageError",
    "PlacementController", "PoisonInputError", "PrefillHandoff",
    "PromotionPipeline", "ReplicaAutoscaler",
    "ReplicaCrashError", "ReplicaHungError", "ServingMetrics",
    "ServingUnavailableError", "StageDeadlineError", "TenantConfig",
    "TenantOverloadedError", "TenantTable", "bundle_path_for",
    "data_fingerprint", "device_fingerprint",
    "enable_compile_cache", "load_bundle", "pow2_buckets", "save_bundle",
    "weights_sha",
]
