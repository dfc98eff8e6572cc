"""Online serving: continuous batching over the compiled decode engine.

The subsystem that turns the offline `run_inference` stack into an
online server (docs/Serving.md):

* :mod:`~tf_yarn_tpu.serving.request` — Request/Response lifecycle, the
  bounded admission queue with backpressure, per-request deadlines.
* :mod:`~tf_yarn_tpu.serving.scheduler` — the slot scheduler: a fixed
  grid of decode slots over one paged KV block pool, one compiled
  device step per tick, free-list slot reuse (continuous, not static,
  batching), int8-transparent storage and a shared prompt-prefix
  cache.
* :mod:`~tf_yarn_tpu.serving.paging` — host-side block-pool free list /
  refcounts, the prefix-cache LRU, and the :class:`HostBlockStore`
  host-RAM tier behind the pool. With ``kv_host_blocks`` > 0
  the scheduler oversubscribes the device pool: under pressure the
  lowest-SLO-tier active stream swaps its KV blocks out to host RAM
  and resumes bit-identically when capacity frees ("KV
  oversubscription & SLO tiers" in docs/Serving.md).

  The scheduler also carries the speculative path (``spec_k > 0``): a
  host-side self-drafter proposes tokens per slot, one compiled
  windowed program verifies them (``models/spec.py``), and each tick
  advances a variable number of tokens per slot — token streams stay
  identical to the exact path. ``decode_attention="fused"`` swaps the
  paged verify forward's attention onto the
  ``paged_int8_decode_attention`` kernel (reads the block pool
  directly; int8 pools only).
* :mod:`~tf_yarn_tpu.serving.server` — the threaded stdlib HTTP
  frontend (``/v1/generate``, ``/healthz``, ``/stats``) and
  `run_serving`, the body of the ``serving`` task type.
* :mod:`~tf_yarn_tpu.serving.prefill` — disaggregated prefill: the
  ``prefill`` task tier runs ONLY bucketed prefill and ships the
  resulting KV blocks to decode replicas over the content-addressed
  block wire; decode's ``PrefillClient`` pulls blocks per long prompt
  and lands them as prefix-cache entries, so admission skips the
  shipped span ("Disaggregated prefill" in docs/Serving.md). Every
  failure mode degrades to local prefill.

Launch through :func:`tf_yarn_tpu.client.run_on_tpu` with a
``ServingExperiment`` and a ``serving`` task spec
(`topologies.serving_topology`); the task advertises its endpoint in
the coordination KV store for discovery.
"""

from tf_yarn_tpu.serving.paging import (  # noqa: F401
    BlockPool,
    HostBlockStore,
    PrefixCache,
)
from tf_yarn_tpu.serving.prefill import (  # noqa: F401
    PrefillClient,
    PrefillServer,
    PrefillTierConfig,
    PrefillWorker,
    kv_prefill_resolver,
    parse_prefill_tier,
    run_prefill,
)
from tf_yarn_tpu.serving.request import (  # noqa: F401
    DEFAULT_TIER,
    FINISH_DEADLINE,
    FINISH_EOS,
    FINISH_ERROR,
    FINISH_LENGTH,
    FINISH_SHUTDOWN,
    TIERS,
    AdmissionQueue,
    QueueFull,
    Request,
    Response,
    RetryAfterEstimator,
    SamplingParams,
    tier_rank,
)
from tf_yarn_tpu.serving.scheduler import SlotScheduler  # noqa: F401
from tf_yarn_tpu.serving.server import (  # noqa: F401
    ServingServer,
    advertised_endpoint,
    run_serving,
)

__all__ = [
    "AdmissionQueue",
    "BlockPool",
    "DEFAULT_TIER",
    "FINISH_DEADLINE",
    "FINISH_EOS",
    "FINISH_ERROR",
    "FINISH_LENGTH",
    "FINISH_SHUTDOWN",
    "HostBlockStore",
    "PrefillClient",
    "PrefillServer",
    "PrefillTierConfig",
    "PrefillWorker",
    "PrefixCache",
    "QueueFull",
    "Request",
    "Response",
    "RetryAfterEstimator",
    "SamplingParams",
    "ServingServer",
    "SlotScheduler",
    "TIERS",
    "advertised_endpoint",
    "kv_prefill_resolver",
    "parse_prefill_tier",
    "run_prefill",
    "run_serving",
    "tier_rank",
]
