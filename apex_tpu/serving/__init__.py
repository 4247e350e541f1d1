"""apex_tpu.serving — continuous-batching decode runtime (ISSUE 9).

The inference-side twin of the training stack: the repo trains GPT at
every parallelism and restores checkpoints onto arbitrary meshes; this
package turns those checkpoints into a *serving* runtime —

- :mod:`.kv_cache` — paged/block KV cache: a pooled
  ``[n_blocks, block, heads, head_dim]`` device arena per layer with a
  host-side :class:`~apex_tpu.serving.kv_cache.BlockAllocator` handing
  fixed-size blocks to requests (the vLLM paging model), sharded over
  the existing ``tp`` axis alongside the tensor-parallel heads.
- :mod:`.paged_attention` — the fused Pallas decode kernel:
  gather-from-block-table (scalar-prefetch index maps, so skipped and
  out-of-range blocks never move HBM bytes) + online-softmax attention
  over the cache in ONE kernel, next to the unfused XLA lowering that is
  its parity reference (``paged_decode_roofline`` of the serving cells
  measures the kernel).
- :mod:`.fused_ops` — the fused dequant/residual/norm epilogue on the
  decode hot path (one VMEM-resident kernel instead of three
  elementwise+reduction HLOs — the operation-fusion paper's decode
  finding, PAPERS.md arxiv 2502.17728).
- :mod:`.model` — prefill/decode split over the *training* layers:
  chunked prefill through the paged multi-query kernel, decode a
  fixed-shape ``[max_batch, spec_width]`` step reusing
  ``ColumnParallelLinear``/``RowParallelLinear`` and RoPE — the
  speculative k+1 verify when drafting is on (ISSUE 13), the classic
  one-token tick when it is not.
- :mod:`.speculative` — self-speculative n-gram / prompt-lookup
  drafting (no second model): host-side proposals verified in-graph
  with per-slot adaptive back-off; rejection rollback is O(1) pointer
  and length moves on the paged cache (never a KV copy).
- :mod:`.scheduler` / :mod:`.engine` — continuous (in-flight)
  batching: requests join and leave mid-flight with ZERO decode-step
  recompiles (all churn is data, never shape), latency
  percentiles/tokens-per-sec through the PR 5 metrics registry, and
  draining on preemption via ``resilience.PreemptionGuard``.
- :mod:`.loader` — restore-from-training-checkpoint through the PR 6
  ``ShardingSpec`` reshard layer (train on mesh N, serve on mesh M).
- :mod:`.lora` — batched multi-LoRA serving (ISSUE 17): per-tenant
  low-rank adapters in a refcounted paged *adapter arena* (the
  BlockAllocator/LRU machinery applied to weights), gathered per batch
  slot inside the one compiled decode/prefill step via the same
  scalar-prefetch index-map trick the paged kernels use — N adapters
  in one batch, zero recompiles, ``adapter_id=None`` bitwise the bare
  engine.
- :mod:`.replica` / :mod:`.fleet` — the fleet layer (ISSUE 11): N
  engine replicas as separate spawned processes (own mesh, own arenas,
  data-service process lifecycle) behind a host-side
  :class:`~apex_tpu.serving.fleet.FleetRouter` with SLO-aware admission
  (priority classes, weighted tenant fairness, typed shed-on-overload),
  failover replay (SIGKILLed replica's in-flight requests re-prefix on
  survivors, greedy-token-identical), and zero-downtime weight rollout
  through the SIGTERM drain + newest-VERIFIED restore.
- :mod:`.transport` — the router↔replica wire made explicit (ISSUE
  14): the Transport duck type the router consumes, with the
  in-process mp-queue shape (``ReplicaProcess``) and a cross-host
  framed-TCP shape — length-prefixed version+crc32 frames (torn or
  corrupted frames are detected and classified as replica failure,
  never deserialized), a :class:`~apex_tpu.serving.transport.
  SocketTransport` client with jittered-backoff reconnect + lossless
  session replay + bounded-outbox backpressure + link-RTT pings, and
  a :func:`~apex_tpu.serving.transport.replica_serve` host daemon
  wrapping the existing replica worker lifecycle.
- :mod:`.autopilot` — the SLO autopilot (ISSUE 18): a jax-free control
  loop beside ``FleetRouter.pump()`` that scales (spawn/drain through
  the ready-handshake and SIGTERM-drain paths, flap quarantine under
  capped back-off), retunes (trace attribution → live engine/router
  knobs via acked broadcast), and canaries every knob change on one
  replica with a paired median-of-ratios A/B judge + automatic
  rollback — every decision a typed timeline event on an injectable
  clock.

See ``docs/serving.md`` for the architecture and cookbook.
"""

from apex_tpu.serving.kv_cache import (
    BlockAllocator,
    CacheGroup,
    KVCacheConfig,
    OutOfBlocksError,
    PrefixCache,
    init_kv_arena,
)
from apex_tpu.serving.paged_attention import (
    paged_attention_decode,
    paged_attention_decode_unfused,
    paged_prefill_attention,
    paged_prefill_attention_unfused,
)
from apex_tpu.serving.lora import (
    AdapterArena,
    LoRAConfig,
    OutOfAdapterSlotsError,
    init_adapter_weights,
    restore_adapter_for_serving,
)
from apex_tpu.serving.model import HybridParams
from apex_tpu.serving.sampling import SamplingParams
from apex_tpu.serving.scheduler import Request, RequestState, Scheduler
from apex_tpu.serving.speculative import (
    NGramProposer,
    SpeculativeConfig,
    ngram_propose,
)
from apex_tpu.serving.engine import ServingConfig, ServingEngine
from apex_tpu.serving.loader import restore_gpt_for_serving
from apex_tpu.serving.replica import ReplicaProcess, ReplicaSpec
from apex_tpu.serving.fleet import FleetRequest, FleetRouter
from apex_tpu.serving.autopilot import (
    AutopilotConfig,
    FleetAutopilot,
    trace_attribution,
)
from apex_tpu.serving.transport import (
    SocketTransport,
    TransportError,
    TransportServer,
    replica_serve,
    start_replica_server,
)

__all__ = [
    "AdapterArena",
    "AutopilotConfig",
    "BlockAllocator",
    "CacheGroup",
    "FleetAutopilot",
    "FleetRequest",
    "FleetRouter",
    "HybridParams",
    "KVCacheConfig",
    "LoRAConfig",
    "NGramProposer",
    "OutOfAdapterSlotsError",
    "OutOfBlocksError",
    "PrefixCache",
    "ReplicaProcess",
    "ReplicaSpec",
    "Request",
    "RequestState",
    "SamplingParams",
    "Scheduler",
    "ServingConfig",
    "ServingEngine",
    "SocketTransport",
    "SpeculativeConfig",
    "TransportError",
    "TransportServer",
    "init_adapter_weights",
    "init_kv_arena",
    "replica_serve",
    "restore_adapter_for_serving",
    "start_replica_server",
    "ngram_propose",
    "paged_attention_decode",
    "paged_attention_decode_unfused",
    "paged_prefill_attention",
    "paged_prefill_attention_unfused",
    "restore_gpt_for_serving",
    "trace_attribution",
]
