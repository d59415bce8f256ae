"""Continuous-batching LM serving (paged block-table KV + scheduler).

Public surface:

- :class:`~paddle_tpu.serving.engine.PagedDecodeEngine` — the
  engine (block-pool KV, chunked prefill interleaved with decode,
  content-hash prefix cache with refcounted blocks + LRU eviction,
  latency/batch tiers, tenant budgets, preempt-to-blocks); build via
  ``PagedDecodeEngine.from_params`` or an artifact's
  ``LMServer.engine()`` (formats v4/v5).
- :class:`~paddle_tpu.serving.engine.SpecDecodeEngine` — the same
  scheduler with a draft model's propose+verify round in place of the
  decode step.
- :class:`~paddle_tpu.serving.engine.EngineRequest` — per-request
  lifecycle record (tokens, TTFT, latency, finish reason,
  prefix_hit_tokens).
- :class:`~paddle_tpu.serving.blocks.BlockPool` — host-side block
  allocator / prefix cache the paged engine schedules over.
- :class:`~paddle_tpu.serving.tiers.TieredStore` — the host-side
  spill tiers behind the HBM block pool (bounded DRAM arena over a
  bounded, checksummed disk directory); LRU-evicted prefix blocks
  demote into it and re-admit bitwise through the import path.
- :class:`~paddle_tpu.serving.router.Router` — the serving-fleet tier:
  prefix-aware placement over N replicas (content-chain block hashes
  as the routing key), three-state-health-driven drain with
  dead-replica requeue, and prefill/decode disaggregation over the
  ``serving/transfer.py`` KV-block wire.
- :class:`~paddle_tpu.serving.replica.EngineReplica` /
  :class:`~paddle_tpu.serving.replica.SocketReplica` /
  :class:`~paddle_tpu.serving.replica.ReplicaServer` /
  :func:`~paddle_tpu.serving.replica.serve_stdio` — the replica
  handles and JSONL transports (stdio with graceful SIGTERM drain,
  TCP for multi-process fleets) the router fronts.
- :func:`~paddle_tpu.serving.sampling.sample_tokens` /
  :func:`~paddle_tpu.serving.sampling.paged_step_fns` — the pure step
  programs (greedy / temperature / top-k inside the compiled step).
"""

from paddle_tpu.serving.blocks import (  # noqa: F401
    BlockPool, chain_hash, prompt_block_hashes)
from paddle_tpu.serving.engine import (  # noqa: F401
    VALID_TIERS, EngineRequest, PagedDecodeEngine, SpecDecodeEngine,
    default_chunk_buckets)
from paddle_tpu.serving.replica import (  # noqa: F401
    EngineLoop, EngineReplica, ReplicaServer, SocketReplica,
    serve_stdio)
from paddle_tpu.serving.router import (  # noqa: F401
    Router, RouterRequest)
from paddle_tpu.serving.tiers import (  # noqa: F401
    TieredStore)
from paddle_tpu.serving.sampling import (  # noqa: F401
    paged_spec_fns, paged_step_fns, sample_tokens, spec_accept,
    spec_verify_tokens)
