"""High-throughput batched serving (reference `paddle/fluid/inference/`
gave AnalysisPredictor a server-side sibling in Paddle Serving; here the
TPU-native equivalent is an in-process engine, because on accelerators
serving throughput *is* dynamic micro-batching into a small set of
pre-compiled bucketed shapes).

`InferenceEngine` wraps `inference.create_predictor`:

- **micro-batcher** — concurrent `submit()` calls coalesce into one
  device batch under `max_batch_size` / `max_batch_delay_ms`; each call
  returns a `concurrent.futures.Future`.
- **pipelined multi-device dispatch** — a shared collector routes
  batches to one dispatch lane per local device (`devices=` / the
  `FLAGS_serving_devices` default), round-robin with a least-inflight
  tiebreak; each lane enqueues the device call asynchronously and a
  completion stage blocks/slices/resolves, so admission, compute, and
  readback overlap (`FLAGS_serving_max_inflight` bounds the pipeline).
- **shape bucketing** — batches pad up to configured batch-size buckets
  (default 1/4/16/64) so XLA compiles exactly once per (device, bucket);
  results are sliced back per request, bit-identical to unbatched runs
  on the same lane+bucket.
- **backpressure & robustness** — bounded queue (`EngineOverloaded`),
  per-request deadlines enforced both while queued AND at completion
  (`ExecutionTimeoutError`), poison isolation per lane, a dead lane
  fails only its own in-flight work and leaves rotation, `shutdown()`
  drains.
- **observability** — `framework.monitor` STAT counters (global +
  per-lane `STAT_serving_lane*`) + streaming latency and in-flight-depth
  histograms, `profiler.RecordEvent` scopes.
- **fault tolerance (ISSUE 15)** — `EngineSupervisor` resurrects a dead
  `GenerationEngine` in place (crash-manifest request replay,
  exactly-once streams, crash-storm breaker, degraded modes), dispatch
  lanes restart per-slot (`FLAGS_serving_lane_restarts`), and
  `failpoints` injects deterministic faults into every hardened seam
  (`FLAGS_failpoints`).
- **router tier (ISSUE 17)** — `Router`: one front door over N
  supervised `GenerationEngine` replicas; prefix-affinity placement
  (blake2b chain digests vs per-replica LRU sketches — session
  stickiness with zero router session state), least-pressure fallback
  on cached `pressure()` snapshots, drain on SLO burn / breaker-open,
  placement-time re-route under typed-failure semantics.
"""
from __future__ import annotations

from ..framework.errors import ResourceExhaustedError


class EngineOverloaded(ResourceExhaustedError):
    """Raised by `InferenceEngine.submit` when the bounded request queue
    is full — explicit load-shedding backpressure, never silent growth."""


from . import failpoints  # noqa: E402
from .engine import EngineConfig, InferenceEngine  # noqa: E402
from .generation import (CrashManifest, GenerationConfig,  # noqa: E402
                         GenerationEngine, ReplayEntry, TokenStream)
from .kv_cache import PagedKVCache  # noqa: E402
from .prefix_cache import PrefixCache, chain_digests  # noqa: E402
from .restart import CrashBreaker, RestartBackoff  # noqa: E402
from .router import Router  # noqa: E402
from .spec_decode import NGramProposer  # noqa: E402
from .supervisor import EngineSupervisor  # noqa: E402

__all__ = ["InferenceEngine", "EngineConfig", "EngineOverloaded",
           "EngineSupervisor", "CrashBreaker", "CrashManifest",
           "GenerationEngine", "GenerationConfig", "NGramProposer",
           "PagedKVCache", "PrefixCache", "ReplayEntry",
           "RestartBackoff", "Router", "TokenStream", "chain_digests",
           "failpoints"]
