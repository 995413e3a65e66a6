"""paddle_tpu.serving — continuous-batching LLM serving.

The three pillars (docs/SERVING.md has the full tour):

- :mod:`.kv_cache` — the paged KV cache: one fixed-shape block pool, a
  refcounted free-list allocator, per-sequence block tables, the
  content-addressed prefix cache (shared blocks, copy-on-write, LRU
  eviction of completed prefixes), and the functional cache views the
  jitted steps thread through the model.
- :mod:`paddle_tpu.kernels.paged_attention` — the ragged paged-attention
  decode kernel (Pallas on TPU, jnp mirror on CPU).
- :mod:`.scheduler` / :mod:`.engine` — continuous batching: admission
  control against *effective* free blocks (free + evictable cached
  prefixes), prefix-hit tail-only prefill, join-on-finish decode slots,
  preempt-and-requeue on pool exhaustion, seeded sampling, streaming
  outputs, and serving counters (TTFT, tokens/s, queue depth, cache
  utilization, prefix-cache hit rate).

Above the single engine sits the fleet plane (docs/SERVING.md
"Fleet serving"):

- :mod:`.router` — :class:`FleetRouter` over N engine replicas
  (:class:`LocalReplica` threads or SIGKILL-able :class:`ProcReplica`
  child processes): health probes, replay-and-suppress failover,
  prefix-affinity + power-of-two-choices placement, priority load
  shedding, and drain/restart under the ElasticSupervisor.
- :mod:`.gateway` — the asyncio HTTP front door: OpenAI-compatible
  ``/v1/completions`` + ``/v1/chat/completions`` with SSE token
  streaming, deadline budgets, and 429/503 backpressure.
- :mod:`.kv_fabric` — the cluster KV fabric (docs/SERVING.md "KV
  fabric"): a fleet-wide prefix directory (epoch/lease-fenced documents
  over the TCPStore telemetry keyspace) so placement lands where a
  prompt's prefix actually lives, plus CRC-verified cross-replica
  KV-block migration (``kv_fetch``/``kv_ingest`` pipe verbs) so hot
  prefixes replicate instead of re-prefilling — strictly advisory,
  every failure mode degrades to local prefill.
- :mod:`.tenancy` — multi-tenant QoS (docs/SERVING.md "Multi-tenancy &
  autoscaling"): API-key -> tenant resolution, per-tenant token-bucket
  rate limits, deficit-round-robin weighted-fair admission
  (:class:`FairQueue`), per-tenant prefix-cache block quotas, and
  roofline cost attribution (FLOPs / HBM bytes / a $-proxy) with
  per-tenant SLO windows.
- :mod:`.autoscaler` — the closed loop over the fleet: Little's-law
  pressure from :meth:`FleetRouter.load_signal` drives replica
  scale-up (gated by the ElasticSupervisor restart budget, warmed via
  the fleet compile cache + KV-fabric migration) and hysteresis-guarded
  scale-down, every decision recorded in the JobLedger.
- :mod:`.workload` — the trace-driven workload engine
  (docs/WORKLOADS.md): seeded, byte-replayable arrival processes
  (Poisson / bursty MMPP / diurnal), heavy-tailed length
  distributions, tenant & prefix-share mixes, and open/closed-loop
  runners that the bench, the soak harness (:mod:`.soak`), and the
  capacity planner all replay from one :class:`WorkloadSpec`.
"""
from ..nn.functional.attention import CacheLayer, StateLayer  # noqa: F401
from . import kv_fabric  # noqa: F401
from .autoscaler import Autoscaler  # noqa: F401
from .engine import LLMEngine, STATS_KEYS, naive_generate  # noqa: F401
from .gateway import Gateway  # noqa: F401
from .journal import Journal, JournalError, JournalTornWrite  # noqa: F401
from .kv_cache import (  # noqa: F401
    BlockAllocator,
    DenseKVCache,
    PagedCacheView,
    PagedKVCache,
)
from .router import (  # noqa: F401
    CircuitBreaker,
    FleetRouter,
    LocalReplica,
    NoHealthyReplica,
    ProcReplica,
    ReplicaState,
    RouterRequest,
    RouterShed,
)
from .scheduler import (  # noqa: F401
    DeadlineExceeded,
    EngineClosed,
    PreemptionStorm,
    QueueFull,
    Request,
    RequestState,
    SamplingParams,
    Scheduler,
)
from .workload import (  # noqa: F401
    ClosedLoopRunner,
    OpenLoopRunner,
    Workload,
    WorkloadError,
    WorkloadRequest,
    WorkloadSpec,
)
from .tenancy import (  # noqa: F401
    AuthError,
    FairQueue,
    Tenant,
    TenantRegistry,
    TokenBucket,
)

__all__ = [
    "LLMEngine", "naive_generate", "STATS_KEYS", "BlockAllocator",
    "CacheLayer", "StateLayer", "PagedKVCache",
    "PagedCacheView", "DenseKVCache", "Request", "RequestState",
    "SamplingParams", "Scheduler", "EngineClosed", "QueueFull",
    "DeadlineExceeded", "PreemptionStorm",
    "FleetRouter", "LocalReplica", "ProcReplica", "ReplicaState",
    "RouterRequest", "RouterShed", "NoHealthyReplica", "Gateway",
    "CircuitBreaker", "Journal", "JournalError", "JournalTornWrite",
    "kv_fabric",
    "Tenant", "TenantRegistry", "TokenBucket", "FairQueue", "AuthError",
    "Autoscaler",
    "WorkloadSpec", "WorkloadRequest", "Workload", "WorkloadError",
    "OpenLoopRunner", "ClosedLoopRunner",
]
