"""Continuous-batching LM decode engine: a slot scheduler over a
paged KV pool.

The lockstep serving surface (``io/lm_serving.LMServer.generate``)
forces every request into one fixed-shape batch: shared prompt length,
shared step count, host-side sampling. :class:`PagedDecodeEngine`
replaces batch formation with SLOTS over a block-table KV layout: the
KV cache is a pool of ``block_size``-token blocks
(``models/transformer.init_block_pool``) and each of the engine's B
slots maps its request's positions onto blocks through a page vector.
A request

1. queues (latency tier ahead of batch tier, per-tenant token budgets)
   until a slot is free and its worst-case block count reserves,
2. prefills in ``chunk_tokens`` chunks
   (``transformer.prefill_into_blocks``), one chunk per ``step()``
   while anything decodes, its tail chunk right-padded to a chunk
   bucket (``core/ragged`` buckets); full prompt blocks already in the
   content-hash prefix cache are mapped, not recomputed,
3. decodes in the shared per-slot-position step
   (``transformer.decode_step_paged`` + on-device sampling) alongside
   whatever else is in flight, each row at its own position,
4. terminates on EOS / max_new and releases the slot and its blocks to
   the next queued request — mid-flight, no other row perturbed. A
   batch-tier request may instead be preempted to blocks for a
   latency-tier one: its pages re-publish into the prefix cache, so
   resume is either a pure host re-mapping or a cache-hit chunked
   prefill, bitwise either way.

Every shape is static: one compile per (chunk bucket, context span) +
ONE for decode, verified by the observe compile tracker under the names
``serving_engine.prefill`` / ``serving_engine.decode``.

The host loop only ever moves ``[B] int32`` token ids off device (the
sampler runs inside the step); scheduling state (positions, active
mask, page table, per-slot temperature/top_k) lives in numpy and is
re-uploaded as tiny vectors per step.

One decode step stays in flight: ``step()`` dispatches decode step n+1
before it reads step n's ids back, so the device always has the next
step queued behind the one the host waits on. Everything step n+1 needs
is known to the host at dispatch except the ids step n samples; a row
that was in step n takes its input from step n's output on the device
(``_next_inputs``). A row whose request may end on EOS stays in step
n+1; if it did end, that row is discarded at read-back, and its pool
write lands in a page the request still held when n+1 was dispatched.

Observability: each engine carries its own metrics ``Registry`` —
queue-wait and time-to-first-token histograms, slot-occupancy and
queue-depth gauges, token/step counters, per-request goodput — and
``serve()`` exposes them on the standard ``/metrics`` + ``/healthz``
endpoints (``observe/health.py``).

:class:`SpecDecodeEngine` adds speculative decoding on top (draft
model sharing the block table, fused k-step propose, batched-window
verify bitwise the decode step).
"""

import dataclasses
import functools
import itertools
import time
from collections import deque
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence

import numpy as np

from paddle_tpu.observe import chrome_trace as _chrome
from paddle_tpu.observe import compile_tracker as _ct
from paddle_tpu.observe import costs as _costs
from paddle_tpu.observe import metrics as _metrics
from paddle_tpu.observe import requests as _requests
from paddle_tpu.observe import trace as _trace
from paddle_tpu.observe.window import SloConfig, WindowedQuantiles
from paddle_tpu.serving import blocks as _blocks

# per-process engine instance counter: bakes into request trace ids
# (``eng<N>.r<rid>``) so several engines' lifecycle events never
# collide in one exported timeline
_ENGINE_IDS = itertools.count()

# decode steps run single-digit ms; prefill tens-to-hundreds (matches
# io/lm_serving's serving-latency resolution)
_LATENCY_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                    0.1, 0.25, 0.5, 1.0, 2.5, 5.0)
_GOODPUT_BUCKETS = (1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0,
                    500.0, 1000.0, 2500.0, 5000.0, 10000.0)
# two decode steps completing further apart than this, with decoders in
# flight throughout, count as one slow step: a stall, not a long program
_SLOW_STEP_S = 1.0

# the host's phases of one engine step: trace scope ``engine/<phase>``,
# series ``engine_<phase>_seconds``. Siblings — no scope encloses them,
# so a device gap in the profiler's trace is named by the phase under it
_PHASES = {
    "ingest": "replica loop: one inbox line parsed and submitted",
    "schedule": "admission, page allocation, adoption, tier promotion, "
                "preemption, and the bookkeeping around a prefill chunk",
    "prefill_chunk": "one prefill (chunk) program dispatched and its "
                     "sampled token read back (behind the decode step "
                     "in flight)",
    "decode_stage": "slot state staged for the decode program: write "
                    "pages, the [B] vectors uploaded, the compile "
                    "tracker's signature",
    "decode_dispatch": "the call into the decode program, until it "
                       "returns, and the cursors moved past it",
    "decode_sync": "the sampled ids of the step before read back: the "
                   "wait for the device and the ids' way to the host",
    "emit": "per-slot loop after the read-back: tokens emitted, "
            "requests finished and recorded",
    "reply": "replica loop: one finished request's result line written "
             "to its sink",
}


class _Phase:
    """One host phase: an ``observe.trace_scope("engine/<name>")`` whose
    seconds also land in the engine's own ``engine_<name>_seconds``.
    ``t0`` / ``end`` are the phase's edges on ``time.perf_counter``, for
    the lifecycle stamps that used to take their own. A phase opened
    inside another (the read-back ``_resolve`` makes under ``schedule``,
    ``ingest`` or ``reply``) keeps its seconds, and the outer phase's
    series leaves them out: no second is counted twice."""

    __slots__ = ("_series", "_scope", "_open", "_inner", "t0", "end")

    def __init__(self, series, name, args, open_):
        self._series = series
        self._scope = _trace.trace_scope("engine/" + name, args=args)
        self._open = open_          # the engine's phases open now
        self._inner = 0.0           # seconds of the phases inside this

    def __enter__(self):
        self.t0 = time.perf_counter()
        self._scope.__enter__()
        self._open.append(self)
        return self

    def __exit__(self, *exc):
        self._open.pop()
        self._scope.__exit__(*exc)
        self.end = time.perf_counter()
        took = self.end - self.t0
        self._series.observe(took - self._inner)
        if self._open:
            self._open[-1]._inner += took
        return False


# ``_last`` holds this where a row's next input is the id the decode
# step in flight samples for it (token ids are never negative)
_FROM_DEVICE = -1


def _next_inputs(ids, last):
    """The decode step's input ids: ``last`` where the host holds a
    row's next input, the step before's sampled id where ``last`` reads
    ``_FROM_DEVICE``. ``ids[:B]`` drops the expert layer's three counts
    that a skeleton's step program appends."""
    import jax.numpy as jnp
    return jnp.where(last < 0, ids[:last.shape[0]], last)


class _InFlight(NamedTuple):
    """A decode step dispatched and not yet read back."""
    ids: object             # its sampled ids (and counts), on the device
    rows: list              # (slot, request, emits) per row it covered,
    #                         as at dispatch; emits False on a replay row
    live: np.ndarray        # live cache rows of each row (pos + 1)
    phases: tuple           # its decode_stage and decode_dispatch
    overlapped: bool        # dispatched with the step before unread


# the two scheduling tiers: "latency" admits ahead of "batch" and may
# preempt a batch-tier victim's blocks; "batch" fills whatever capacity
# latency traffic leaves (and is the only tier preemption may evict)
VALID_TIERS = ("latency", "batch")


@dataclasses.dataclass
class EngineRequest:
    """One generation request and its lifecycle record."""
    rid: int
    prompt: np.ndarray                  # [Tp] int32
    max_new: int
    temperature: float = 0.0
    top_k: int = 0
    eos_id: Optional[int] = None
    tenant: str = "default"             # token-budget accounting key
    tier: str = "batch"                 # latency | batch (VALID_TIERS)
    # -- lifecycle (filled by the engine) --------------------------------
    slot: int = -1
    prefix_hit_tokens: int = 0          # prompt tokens served from the
    #                                     prefix cache
    block_hashes: Optional[List[bytes]] = None  # prompt block digests,
    #                                     memoized at first admission try
    tier_promote_done: bool = False     # spill-tier promotion attempted
    #                                     (once per request: a blocked
    #                                     queue head re-enters admission
    #                                     every step)
    tier_promoted_blocks: int = 0       # blocks that promotion just
    #                                     re-adopted for this request —
    #                                     admission labels them dram/
    #                                     disk hits, not hbm
    tokens: List[int] = dataclasses.field(default_factory=list)
    status: str = "queued"              # queued | prefilling (mid-
    #                                     chunk) | running | done
    finish_reason: Optional[str] = None  # eos | max_tokens
    submit_t: float = 0.0
    prefill_t: Optional[float] = None
    first_token_t: Optional[float] = None
    finish_t: Optional[float] = None
    prefill_own_s: float = 0.0          # device time of this request's
    #                                     OWN prefill chunk(s)
    trace_id: str = ""                  # eng<N>.r<rid>: joins this
    #                                     request's lifecycle events
    decode_open: bool = False           # a "decode" trace slice is open
    preemptions: int = 0                # times preempted to blocks
    # preempt-to-blocks resume state: the host snapshot
    # taken at preemption (block-chain digests + decode cursor), and —
    # on the eviction-fallback path — the already-emitted tokens the
    # replay force-feeds through the decode program without re-emitting
    snapshot: Optional[dict] = dataclasses.field(
        default=None, repr=False)
    replay: Optional[List[int]] = dataclasses.field(
        default=None, repr=False)

    @property
    def output(self) -> np.ndarray:
        """prompt + generated ids, the ``generate()``-shaped result."""
        return np.concatenate([self.prompt,
                               np.asarray(self.tokens, np.int32)])

    @property
    def ttft_s(self) -> Optional[float]:
        if self.first_token_t is None:
            return None
        return self.first_token_t - self.submit_t

    @property
    def latency_s(self) -> Optional[float]:
        if self.finish_t is None:
            return None
        return self.finish_t - self.submit_t

    @property
    def queue_wait_s(self) -> Optional[float]:
        if self.prefill_t is None:
            return None
        return self.prefill_t - self.submit_t

    @property
    def prefill_stall_s(self) -> Optional[float]:
        """Admitted -> first token, minus own prefill device time:
        time parked behind OTHER requests' chunks and the decode steps
        interleaved between them."""
        if self.first_token_t is None or self.prefill_t is None:
            return None
        return max(self.first_token_t - self.prefill_t
                   - self.prefill_own_s, 0.0)

    @property
    def decode_s(self) -> Optional[float]:
        if self.finish_t is None or self.first_token_t is None:
            return None
        return self.finish_t - self.first_token_t

    @property
    def cache_hit_frac(self) -> float:
        """Fraction of the prompt served from the prefix cache."""
        return self.prefix_hit_tokens / max(int(self.prompt.size), 1)


def _decode_step_flops(decode_fn, params, pool, batch, pages):
    """Model FLOPs of one compiled decode step from the lowered HLO
    cost model (None when unavailable) — the ``decode_mfu()``
    numerator an in-process engine derives itself; AOT artifacts
    carry it stamped in ``meta.cost_analysis`` instead."""
    vec_i = np.zeros(batch, np.int32)
    vec_f = np.zeros(batch, np.float32)
    vec_b = np.zeros(batch, bool)
    cost = _costs.lowered_cost(
        decode_fn, params, pool, vec_i, vec_i, vec_b, pages,
        vec_f, vec_i, np.int32(0))
    return (cost or {}).get("flops")


def _tree_nbytes(tree) -> int:
    import jax
    return sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(tree))


def default_chunk_buckets(chunk_tokens: int) -> tuple:
    """Power-of-two chunk buckets up to ``chunk_tokens`` (which is
    always included): a prompt's tail chunk pads to the smallest
    covering bucket instead of the full chunk size."""
    out, b = {int(chunk_tokens)}, 8
    while b < chunk_tokens:
        out.add(b)
        b *= 2
    return tuple(sorted(out))


class PagedDecodeEngine:
    """Block-table continuous batching: paged KV, chunked prefill,
    prefix cache, tiers and tenant budgets.

    ``prefill`` / ``decode`` follow the ``sampling.paged_step_fns``
    signatures (params threaded explicitly, the pool donated and
    rebound from every result). Build one with :meth:`from_params`
    (in-process jit) or :meth:`io.lm_serving.LMServer.engine` (AOT
    artifact). The KV cache is a block POOL
    (``models/transformer.init_block_pool``): HBM is committed per
    ``block_size``-token block actually written — a request holds
    ``ceil((Tp + max_new)/block_size)`` blocks, not a whole
    ``cache_len`` row — and the pool can be sized independently of
    ``batch``. On top of the pool:

    - **chunked prefill** — prompts are admitted in ``chunk_tokens``
      chunks (``transformer.prefill_into_blocks``), ONE chunk per
      ``step()`` interleaved with the batched decode step, so a long
      prompt does not stall in-flight decoders for its full duration,
      and any prompt with ``Tp + max_new <= cache_len`` is accepted;
    - **prefix cache** — full prompt blocks are published under
      content-chain hashes (``serving/blocks``); a later prompt sharing
      the prefix maps the cached blocks into its page table with a
      refcount bump and skips their prefill compute. Refcount-0 cached
      blocks park in an LRU and are evicted oldest-first under
      allocation pressure. Hit decoding is bitwise the cold-prefill
      decoding (the gathered KV values are identical).

    Admission reserves a request's worst-case block count up front and
    allocates lazily, so decode never stalls mid-flight on an empty
    pool; a request that cannot reserve waits FIFO at the queue head.
    Compile discipline: at most one compile per (chunk bucket, context
    span) pair — the chunk grid is fixed at ``chunk_tokens``, so the
    reachable spans are the multiples of ``chunk_tokens`` below
    ``cache_len`` — plus ONE decode (same tracker names,
    ``compile_counts()``). Span specialization is what keeps a COLD
    chunk's attention at ``C x C`` instead of ``C x cache_len``.
    """

    def __init__(self, prefill: Callable, decode: Callable, params,
                 cache, *, batch: int, cache_len: int, block_size: int,
                 num_blocks: Optional[int] = None,
                 chunk_tokens: int = _blocks.DEFAULT_CHUNK_TOKENS,
                 chunk_buckets: Optional[Sequence[int]] = None,
                 seed: Optional[int] = None,
                 registry: Optional[_metrics.Registry] = None,
                 tracker: Optional[_ct.CompileTracker] = None,
                 slo: Optional[SloConfig] = None,
                 decode_flops: Optional[float] = None,
                 pallas_mode: Optional[str] = None,
                 kernel_paths: Optional[Dict[str, Dict[str, str]]] = None,
                 kv_dtype: Optional[str] = None,
                 tenant_budgets: Optional[Dict[str, int]] = None,
                 tiers=None,
                 conform: Optional[Callable] = None):
        bs = int(block_size)
        if bs < 1 or cache_len % bs:
            raise ValueError(f"cache_len {cache_len} must be a positive "
                             f"multiple of block_size {bs}")
        chunk_tokens = min(int(chunk_tokens), int(cache_len))
        if chunk_tokens < 1:
            raise ValueError(f"chunk_tokens must be >= 1, "
                             f"got {chunk_tokens}")
        # the chunk grid anchors the static context spans: chunk
        # boundaries (and therefore prefix-hit cutoffs) must land on
        # block edges, and the grid must tile cache_len so every page
        # vector a chunk needs fits in pages_per_slot
        if chunk_tokens % bs:
            raise ValueError(f"chunk_tokens {chunk_tokens} must be a "
                             f"multiple of block_size {bs}")
        if cache_len % chunk_tokens:
            raise ValueError(f"cache_len {cache_len} must be a multiple "
                             f"of chunk_tokens {chunk_tokens}")
        if chunk_buckets is None:
            chunk_buckets = default_chunk_buckets(chunk_tokens)
        if tracker is None:
            # per-engine tracker by default: a shared (global) tracker
            # would have seen another engine's signatures already and
            # mis-credit / swallow this engine's real compiles in
            # compile_counts(). The engine LEGITIMATELY compiles one
            # prefill program per reachable (chunk bucket, context
            # span) pair — raise the default tracker's storm threshold
            # past that ceiling so normal chunk-grid traffic doesn't
            # read as a recompile storm (a caller-supplied tracker
            # keeps its own threshold)
            spans = max(1, int(cache_len) // chunk_tokens)
            tracker = _ct.CompileTracker(
                storm_threshold=spans * len(tuple(chunk_buckets)) + 2)
        import jax
        import jax.numpy as jnp
        self._jnp = jnp
        self._prefill_fn = prefill
        self._decode_fn = decode
        # a plain jit, not an exported program: the benchmark tells the
        # decode program from the prefill chunks as the most frequent
        # ``jit_call_exported`` module
        self._next_inputs = jax.jit(_next_inputs)
        self._inflight: Optional[_InFlight] = None
        # requests finished by a read-back outside ``step()`` (a resolve
        # for ``_preempt`` or ``export_prefix``): the next step returns
        # them
        self._landed: List[EngineRequest] = []
        self.cache = cache
        self.batch = int(batch)
        self.cache_len = int(cache_len)
        # decode-MFU accounting (the PR-2 scoreboard): model FLOPs of
        # one compiled decode step (from lowered cost analysis or the
        # artifact's cost stamp) against the declared chip peak
        self.decode_flops = decode_flops
        self._peak_flops = _costs.device_peak_flops()
        # the resolved PADDLE_TPU_PALLAS policy the programs were built
        # under (None = unknown: an artifact without the stamp)
        self.pallas_mode = pallas_mode
        # per compiled program, the path each kernel site ACTUALLY
        # placed ("pallas" | "pallas_interpret" | "xla"): recorded by
        # the step functions as they trace (``sampling._recorded``) or
        # stamped into the artifact at export. A live dict — in-process
        # programs appear as they first trace.
        self.kernel_paths = kernel_paths if kernel_paths is not None \
            else {}
        self.buckets = tuple(sorted({int(b) for b in chunk_buckets
                                     if int(b) <= cache_len}))
        if not self.buckets:
            raise ValueError(f"no chunk bucket fits cache_len="
                             f"{cache_len} (chunk_buckets="
                             f"{tuple(chunk_buckets)})")
        # engine-level "unseeded must not repeat": like the LMServer fix,
        # None draws fresh OS entropy instead of collapsing to a constant
        self._rng = np.random.RandomState(seed)
        self._tracker = tracker
        # -- host-side slot state (uploaded as [B] vectors per step) -----
        B = self.batch
        self._pos = np.zeros(B, np.int32)
        self._active = np.zeros(B, bool)
        self._last = np.zeros(B, np.int32)
        self._temp = np.zeros(B, np.float32)
        self._topk = np.zeros(B, np.int32)
        self._slot_req: List[Optional[EngineRequest]] = [None] * B
        self._free = deque(range(B))
        self._queue: deque = deque()
        self._ids = itertools.count()
        # -- request-scoped observability --------------------------------
        self._engine_id = next(_ENGINE_IDS)
        # perf_counter -> wall-clock anchor: lifecycle events must land
        # on the same epoch timeline as the trace-scope spans, but the
        # engine's internal timestamps stay monotonic perf_counter
        self._wall_anchor = time.time() - time.perf_counter()
        self.request_log = _requests.RequestLog()
        self.slo: Optional[SloConfig] = None
        self._win_ttft: WindowedQuantiles = None  # set by configure_slo
        self._win_tps: WindowedQuantiles = None
        self.configure_slo(slo)
        # -- metrics ------------------------------------------------------
        reg = self.metrics = registry or _metrics.Registry()
        self._m_requests = reg.counter(
            "engine_requests_total", "requests submitted")
        self._m_completed = reg.counter(
            "engine_requests_completed_total",
            "requests finished, by termination reason")
        self._m_tokens = reg.counter(
            "engine_tokens_total", "tokens emitted across all requests")
        self._m_steps = reg.counter(
            "engine_decode_steps_total", "batched decode steps executed")
        self._m_prefills = reg.counter(
            "engine_prefill_calls_total", "slot prefills executed")
        self._m_queue = reg.gauge(
            "engine_queue_depth", "requests waiting for a slot")
        self._m_occupancy = reg.gauge(
            "engine_slots_active", "slots currently decoding")
        self._m_wait_s = reg.histogram(
            "engine_queue_wait_seconds", "submit -> prefill-start wait",
            buckets=_LATENCY_BUCKETS)
        self._m_ttft_s = reg.histogram(
            "engine_ttft_seconds", "submit -> first token (queue wait + "
            "prefill)", buckets=_LATENCY_BUCKETS)
        self._m_prefill_s = reg.histogram(
            "engine_prefill_seconds", "slot-prefill device latency",
            buckets=_LATENCY_BUCKETS)
        self._m_step_s = reg.histogram(
            "engine_decode_step_seconds", "batched decode-step latency: "
            "its stage, dispatch and [B]-ids host sync phases",
            buckets=_LATENCY_BUCKETS)
        self._m_overlapped = reg.counter(
            "engine_decode_overlapped_total", "decode steps dispatched "
            "while the step before was still unread: the device had "
            "this step queued behind the one the host waited on")
        self._m_goodput = reg.histogram(
            "engine_request_tokens_per_sec", "per-request goodput: "
            "tokens emitted / (finish - submit)",
            buckets=_GOODPUT_BUCKETS)
        self._m_win_ttft = reg.gauge(
            "engine_ttft_window_seconds", "rolling TTFT quantile over "
            "the SLO window (label q = p50|p95|p99) — the cumulative "
            "histogram cannot answer this once traffic has history")
        self._m_win_tps = reg.gauge(
            "engine_tokens_per_sec_window", "rolling per-request "
            "goodput quantile over the SLO window (label q)")
        self._m_burn = reg.gauge(
            "engine_slo_burn_rate", "TTFT SLO burn rate: windowed "
            "violation fraction / error budget (0 without a "
            "configured SLO)")
        self._m_rejected = reg.counter(
            "engine_requests_rejected_total",
            "submissions rejected at validation, by reason")
        self._m_phase = {
            name: reg.histogram(f"engine_{name}_seconds", help,
                                buckets=_LATENCY_BUCKETS)
            for name, help in _PHASES.items()}
        self._m_slow_steps = reg.counter(
            "engine_slow_steps_total", f"decode steps that completed "
            f"more than {_SLOW_STEP_S:g} s after the one before, with "
            f"decoders in flight throughout")
        self._m_live_rows = reg.counter(
            "engine_decode_live_rows_total", "live cache rows of the "
            "active slots (positions 0 .. pos), summed over decode "
            "steps: what a decode step's attention has to read")
        self._m_read_rows = reg.counter(
            "engine_decode_read_rows_total", "cache rows the decode "
            "program's attention reads, summed over decode steps: "
            "slots x cache_len where it attends over the gathered "
            "view of every slot's pages (the XLA path), the active "
            "slots' live pages where a kernel reads pages in place")
        self._step_end: Optional[float] = None   # last decode step's,
        #                                 while decoders stay in flight
        self._tag = {"step": 0, "active": 0}     # span args of this step
        self._phases_open: List[_Phase] = []
        # -- block pool and page table -----------------------------------
        self.block_size = bs
        self.pages_per_slot = cache_len // bs
        self.num_blocks = int(num_blocks if num_blocks is not None
                              else batch * self.pages_per_slot)
        self.chunk_tokens = chunk_tokens
        self.pool = _blocks.BlockPool(self.num_blocks, bs)
        # KV storage width of the device pool ("none" = model dtype;
        # "int8"/"int4" pools carry per-(position, head) scale tables
        # the page table indexes alongside the values). Derived HBM
        # arithmetic uses the pool SHAPES under the one description of
        # a pool (``serving/transfer``: page tables, and whatever else
        # is rows per slot), so it needs no model config.
        from paddle_tpu.serving import transfer as _transfer
        self.kv_dtype = kv_dtype or "none"
        self.kv_bytes_per_token = _transfer.bytes_per_token(cache)
        self.pool_bytes = self.kv_bytes_per_token * self.num_blocks * bs
        # A model with RECURRENT layers (``models/gated_hybrid``): the
        # pool pytree also holds, per slot, fixed-size state rows, and
        # the page tables cover the attention layers only. Pages are
        # then NOT all the state a
        # position depends on: nothing stores the recurrent rows at a
        # block boundary, so a prefix hit could not be resumed from.
        # Prefix publishing, lookup, adoption, export/import and tier
        # demotion are off; preemption releases the pages unpublished
        # and resume replays from position 0.
        self.recurrent_state_bytes = _transfer.slot_state_bytes(cache)
        self.recurrent = self.recurrent_state_bytes > 0
        if self.recurrent and tiers is not None:
            raise ValueError("tiered spill (tiers=) is off for a model "
                             "with recurrent state: no prefix block is "
                             "ever published")
        # the expert layer's three counters, made when a step program
        # first returns counts after its ids (``_moe_counters``)
        self._m_moe = None
        # page table uploaded on change (most decode steps reuse the
        # cached device copy); unallocated entries stay 0 and are only
        # ever read under the attend mask
        self._pages = np.zeros((B, self.pages_per_slot), np.int32)
        self._pages_dev = None
        self._nalloc = [0] * B              # pages allocated per slot
        self._slot_blocks: List[List[int]] = [[] for _ in range(B)]
        self._slot_hashes: List[List[bytes]] = [[] for _ in range(B)]
        self._slot_off = [0] * B            # next prompt token to prefill
        self._slot_reserved = [0] * B       # unallocated reservation left
        self._slot_prefill_s = [0.0] * B    # device seconds across chunks
        self._prefilling: deque = deque()   # slots mid-prompt, round-robin
        self._evictions_seen = 0
        # -- multi-tenant scheduling state -------------------------------
        # budgets cap a tenant's RESERVED tokens in flight (admitted,
        # unfinished requests' prompt+max_new); exhaustion queues the
        # tenant's requests — other tenants admit past them
        self.tenant_budgets: Dict[str, int] = dict(tenant_budgets or {})
        self._tenant_used: Dict[str, int] = {}
        self._preempted: deque = deque()    # preempted reqs awaiting resume
        self._slot_forced: List[deque] = [deque() for _ in range(B)]
        self._m_preempts = reg.counter(
            "engine_preemptions_total", "batch-tier victims preempted "
            "to blocks (pages re-published to the prefix cache) so a "
            "latency-tier request could reserve")
        self._m_resumes = reg.counter(
            "engine_resumes_total", "preempted requests resumed, by "
            "mode: remap = every snapshot block still cached (pure "
            "host re-mapping), replay = eviction fallback (cache-hit "
            "chunked prefill + forced decode replay)")
        self._m_tenant_tokens = reg.gauge(
            "engine_tenant_tokens_in_flight", "reserved tokens "
            "(prompt + max_new of live requests) per tenant — what the "
            "token budget caps")
        self._m_blocks_in_use = reg.gauge(
            "engine_blocks_in_use", "pool blocks referenced by live "
            "requests")
        self._m_blocks_free = reg.gauge(
            "engine_blocks_free", "pool blocks holding nothing (not "
            "even evictable cached content)")
        self._m_blocks_cached = reg.gauge(
            "engine_blocks_cached", "refcount-0 prefix-cache blocks "
            "parked in the LRU (evictable)")
        self._m_prefix_hits = reg.counter(
            "engine_prefix_cache_hit_blocks_total",
            "prompt blocks served from the prefix cache (prefill "
            "compute skipped)")
        self._m_prefix_miss = reg.counter(
            "engine_prefix_cache_miss_blocks_total",
            "full prompt blocks that had to be prefilled")
        self._m_evictions = reg.counter(
            "engine_prefix_cache_evictions_total",
            "cached blocks evicted LRU-oldest-first under allocation "
            "pressure")
        self._m_chunks = reg.counter(
            "engine_prefill_chunks_total", "prefill chunk programs "
            "executed (several per long prompt)")
        self._m_stall = reg.histogram(
            "engine_prefill_stall_seconds", "time in-flight decoders "
            "were stalled by one prefill chunk (observed per chunk run "
            "while any slot was decoding)", buckets=_LATENCY_BUCKETS)
        self._m_kv_bytes = reg.gauge(
            "engine_kv_bytes_per_token", "pool HBM bytes one resident "
            "token costs across all layers (k + v + scale rows at the "
            "pool's kv_dtype) — the per-token decode-read traffic and "
            "the slots-at-equal-HBM denominator")
        self._m_kv_bytes.set(self.kv_bytes_per_token)
        reg.gauge("engine_kv_pool_bytes", "HBM bytes of the paged K/V "
                  "pool (the attention layers' pages)"
                  ).set(self.pool_bytes)
        reg.gauge("engine_recurrent_state_bytes", "HBM bytes of the "
                  "per-slot recurrent rows beside the pool (0 for a "
                  "model without recurrent layers)"
                  ).set(self.recurrent_state_bytes)
        self._m_weight_bytes = reg.gauge(
            "engine_weight_bytes", "bytes of the parameter tree(s) the "
            "engine hands its step programs (what a decode step streams "
            "is at most this)")
        self._conform = conform
        self.params = params
        self._m_kv_exported = reg.counter(
            "engine_kv_blocks_exported_total", "prefix-cache blocks "
            "serialized out over the P/D transfer wire "
            "(export_prefix — the prefill half of disaggregation)")
        self._m_kv_imported = reg.counter(
            "engine_kv_blocks_imported_total", "transferred blocks "
            "adopted into the pool via the prefix-cache publish path "
            "(import_prefix — the decode half of disaggregation)")
        self._m_tier_hits = reg.counter(
            "engine_prefix_tier_hit_blocks_total", "prompt blocks "
            "served per tier (label tier): hbm = ordinary prefix-cache "
            "hit, dram/disk = spilled block re-adopted at admission")
        self._m_tier_miss = reg.counter(
            "engine_prefix_tier_miss_blocks_total", "prefix lookups "
            "that missed a tier (label tier), counted once per "
            "request's promotion walk — a cold block misses hbm, dram "
            "AND disk; a disk re-adopt misses hbm and dram")
        # -- tiered spill store (HBM -> host DRAM -> disk) ---------------
        # `tiers` is a serving.tiers.TieredStore (tests that want
        # direct store access) or a kwargs dict for one ({"dram_bytes":
        # ..., "disk_bytes": ..., "disk_dir": ...}); None (the default)
        # disables spill entirely — eviction behaves exactly as before.
        self.tiers = None
        if tiers is not None:
            from paddle_tpu.serving import tiers as _tiers
            self.tiers = (tiers if isinstance(tiers, _tiers.TieredStore)
                          else _tiers.TieredStore(registry=reg,
                                                  **dict(tiers)))
            self.pool.on_evict = self._demote_block

    draft_params = None     # a SpecDecodeEngine's second tree

    @property
    def params(self):
        """The parameter tree handed to every step program. Whatever is
        assigned (at construction or later: a benchmark's next seed,
        training weights) passes ONCE through ``conform``, which
        brings it to the form the programs take — for an artifact's
        engine the dtypes of the decode program's own inputs
        (``io/lm_serving._conform``), in-process
        ``transformer.compute_dtype_params`` — so that no step casts a
        float32 matrix; ``None`` (a caller freeing the weights) is
        kept."""
        return self._params

    @params.setter
    def params(self, tree):
        if tree is not None and self._conform is not None:
            tree = self._conform(tree)
        self._params = tree
        self._m_weight_bytes.set(_tree_nbytes((tree, self.draft_params)))

    # -- construction ------------------------------------------------------
    @classmethod
    def from_params(cls, params, cfg, *, batch: int, cache_len: int,
                    block_size: int = _blocks.DEFAULT_BLOCK_SIZE,
                    num_blocks: Optional[int] = None,
                    chunk_tokens: int = _blocks.DEFAULT_CHUNK_TOKENS,
                    chunk_buckets: Optional[Sequence[int]] = None,
                    seed: Optional[int] = None,
                    pallas: Optional[str] = None,
                    kv_dtype: Optional[str] = None, **kw):
        """In-process paged engine: jit the chunk-prefill/paged-decode
        programs against live params (the no-artifact path tests and
        benchmarks drive). ``pallas`` overrides the
        ``PADDLE_TPU_PALLAS`` policy for the step programs (flash-decode
        attention + chunk-prefill kernel + fused sampling epilogue);
        ``params`` may be the ``quantize_lm_params`` int8 tree — the
        decode step then reads weights at 1 byte/elt (in-scan dequant).
        ``kv_dtype`` ("int8"/"int4") quantizes the KV pool itself
        (``transformer.init_block_pool``): history streams at 1 or 1/2
        byte/elt and the same HBM budget holds 4-8x the blocks — the
        step programs detect the pool layout from the pytree, so no
        other wiring changes."""
        import jax
        from paddle_tpu.models import transformer
        from paddle_tpu.ops.pallas import policy as _pallas_policy
        from paddle_tpu.serving import sampling
        if cache_len > cfg.max_len:
            raise ValueError(f"cache_len {cache_len} exceeds cfg.max_len "
                             f"{cfg.max_len}")
        if block_size < 1 or cache_len % block_size:
            raise ValueError(f"cache_len {cache_len} must be a positive "
                             f"multiple of block_size {block_size}")
        nb = int(num_blocks if num_blocks is not None
                 else batch * (cache_len // block_size))
        # as an artifact stores them: the block matrices cast once,
        # not by every step (here already: the flops below are those
        # of the program the engine runs)
        conform = functools.partial(transformer.compute_dtype_params,
                                    cfg=cfg)
        params = conform(params)
        prefill_fn, decode_fn = sampling.paged_step_fns(
            cfg, block_size, pallas=pallas)
        pool = transformer.init_block_pool(cfg, nb, block_size,
                                           kv_dtype=kv_dtype, slots=batch)
        # the pool (argument 1) is donated: both programs update it in
        # place and the engine rebinds self.cache from every result
        jdf = jax.jit(decode_fn, donate_argnums=(1,))
        if "decode_flops" not in kw:    # the trace is not free — skip
            pages = np.zeros((batch, cache_len // block_size), np.int32)
            kw["decode_flops"] = _decode_step_flops(
                jdf, params, pool, batch, pages)
        return cls(jax.jit(prefill_fn, donate_argnums=(1,)), jdf, params,
                   pool,
                   batch=batch, cache_len=cache_len,
                   block_size=block_size, num_blocks=nb,
                   chunk_tokens=chunk_tokens, chunk_buckets=chunk_buckets,
                   seed=seed, kv_dtype=kv_dtype,
                   pallas_mode=_pallas_policy.pallas_mode(pallas),
                   kernel_paths=decode_fn.kernel_paths, conform=conform,
                   **kw)

    # -- request-scoped observability --------------------------------------
    def configure_slo(self, slo: Optional[SloConfig]):
        """Install (or with ``None`` clear) the TTFT SLO this engine's
        `/healthz` evaluates over its rolling window. Resets the window
        estimators to the new window length — callable after
        construction (the ``paddle_tpu serve --ttft_slo_ms`` path)."""
        self.slo = slo
        win = slo.window_s if slo is not None else 60.0
        self._win_ttft = WindowedQuantiles(window_s=win)
        self._win_tps = WindowedQuantiles(window_s=win)
        # per-tier TTFT windows (created lazily as tiers appear) feed
        # the {q, tier}-labelled gauge samples: the scheduler's whole
        # point is per-tier p99 separation, which the aggregate window
        # cannot show
        self._win_ttft_tier: Dict[str, WindowedQuantiles] = {}
        self._tier_window_s = win

    def _tier_window(self, tier: str) -> WindowedQuantiles:
        win = self._win_ttft_tier.get(tier)
        if win is None:
            win = self._win_ttft_tier[tier] = WindowedQuantiles(
                window_s=self._tier_window_s)
        return win

    def _wall(self, perf_t: float) -> float:
        return self._wall_anchor + perf_t

    def _ev(self, req: EngineRequest, name: str, ph: str, perf_t: float,
            **args):
        """One lifecycle event on this request's async trace track."""
        _chrome.record_event(name, self._wall(perf_t), ph, req.trace_id,
                             args=args or None)

    def phase(self, name: str, **args) -> _Phase:
        """Context manager over one of the step's host phases
        (``_PHASES``); ``args`` join the step's number and active-slot
        count on the recorded span."""
        return _Phase(self._m_phase[name], name,
                      dict(self._tag, **args) if args else self._tag,
                      self._phases_open)

    def _reject(self, rid: int, reason: str, msg: str) -> ValueError:
        """Account + trace a rejected submission; returns (does not
        raise) the ValueError so call sites read ``raise self._reject``."""
        now = time.perf_counter()
        self._m_rejected.inc(reason=reason)
        _chrome.record_event(
            "request_rejected", self._wall(now), "n",
            f"eng{self._engine_id}.r{rid}",
            args={"rid": rid, "reason": reason})
        # a rejection leaves a record too (observe/requests.py promises
        # one per finished OR rejected request): no measured components,
        # so attribute() reports dominance "none" and slowest(by latency)
        # skips it, but a rejection storm shows in summary()'s by_reason
        rec = {"rid": rid, "engine": self._engine_id,
               "trace_id": f"eng{self._engine_id}.r{rid}",
               "submit_ts": round(self._wall(now), 6),
               "finish_reason": f"rejected:{reason}",
               "prompt_tokens": None, "tokens": 0,
               "queue_wait_s": None, "prefill_own_s": None,
               "prefill_stall_s": None, "decode_s": None,
               "ttft_s": None, "latency_s": None, "cache_hit_frac": 0.0}
        self.request_log.add(rec)
        _requests.default_request_log().add(rec)
        return ValueError(msg)

    def _record_request(self, req: EngineRequest):
        """One flat record into the engine's bounded request ring AND
        the process default (``observe.default_request_log()``)."""
        def r6(v):
            return round(v, 6) if v is not None else None

        rec = {"rid": req.rid, "engine": self._engine_id,
               "trace_id": req.trace_id,
               "submit_ts": round(self._wall(req.submit_t), 6),
               "finish_reason": req.finish_reason,
               "tenant": req.tenant, "tier": req.tier,
               "preemptions": req.preemptions,
               "prompt_tokens": int(req.prompt.size),
               "tokens": len(req.tokens),
               "queue_wait_s": r6(req.queue_wait_s),
               "prefill_own_s": r6(req.prefill_own_s),
               "prefill_stall_s": r6(req.prefill_stall_s),
               "decode_s": r6(req.decode_s),
               "ttft_s": r6(req.ttft_s),
               "latency_s": r6(req.latency_s),
               "cache_hit_frac": round(req.cache_hit_frac, 4)}
        self.request_log.add(rec)
        _requests.default_request_log().add(rec)

    def _slo_burn_rate(self) -> float:
        if self.slo is None:
            return 0.0
        return self.slo.burn_rate(
            self._win_ttft.fraction_over(self.slo.ttft_s))

    def _update_window_gauges(self):
        """Refresh the rolling-quantile gauges + burn rate. Called when
        requests finish (request-grain, not step-grain, so the sort
        stays off the per-token path) AND on every read of the gauges
        (``health()`` / ``metrics_text()``): window samples expire with
        time, so a gauge last written mid-breach would otherwise report
        that breach forever once traffic stops, contradicting the
        live-computed `/healthz`."""
        ttft = self._win_ttft.quantiles((0.5, 0.95, 0.99))
        tps = self._win_tps.quantiles((0.5, 0.95, 0.99))
        for lbl, q in (("p50", 0.5), ("p95", 0.95), ("p99", 0.99)):
            self._m_win_ttft.set(ttft[q], q=lbl)
            self._m_win_tps.set(tps[q], q=lbl)
        # per-tier split of the same gauge ({q, tier} samples): the
        # scheduler's effect IS the separation between these series
        for tier, win in self._win_ttft_tier.items():
            tq = win.quantiles((0.5, 0.95, 0.99))
            for lbl, q in (("p50", 0.5), ("p95", 0.95), ("p99", 0.99)):
                self._m_win_ttft.set(tq[q], q=lbl, tier=tier)
        self._m_burn.set(self._slo_burn_rate())

    # -- request API -------------------------------------------------------
    def set_tenant_budget(self, tenant: str, tokens: Optional[int]):
        """Cap (or with ``None`` uncap) ``tenant``'s reserved tokens in
        flight. Takes effect at the next admission — live requests are
        never evicted by a budget change (budgets queue, they do not
        kill). Submissions whose own prompt+max_new exceeds the cap are
        REJECTED (reason ``exceeds_budget``) — they could never admit;
        note that shrinking a budget below an already-QUEUED request's
        charge parks that request until the budget is raised again.
        Per-tenant gauge samples exist only for budgeted tenants;
        uncapping drops the sample (it would otherwise freeze at its
        last value)."""
        if tokens is None:
            self.tenant_budgets.pop(tenant, None)
            self._m_tenant_tokens.remove(tenant=tenant)
        else:
            self.tenant_budgets[str(tenant)] = int(tokens)

    def submit(self, prompt, max_new: int, *, temperature: float = 0.0,
               top_k: int = 0, eos_id: Optional[int] = None,
               tenant: str = "default", tier: str = "batch",
               trace: Optional[str] = None) -> EngineRequest:
        """Queue one request; returns its (live) EngineRequest record.
        Any prompt with ``len(prompt) + max_new <= cache_len`` is
        accepted and prefilled in chunks. ``tier="latency"`` admits
        ahead of batch-tier work and may preempt a batch victim's
        blocks under pool pressure; ``tenant`` charges the request's
        worst-case tokens against that tenant's budget (exhaustion
        queues, never rejects). ``trace`` adopts a caller-provided
        trace id verbatim (the fleet router propagating its
        fleet-unique context over the serve wire, so the engine's
        lifecycle events join the router's ``route``/``place`` spans in
        one merged timeline) instead of minting the per-process
        ``eng<N>.r<rid>``. A malformed request — the classes a JSONL
        wire can deliver — is a counted rejection, never a
        traceback."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        rid = next(self._ids)
        if prompt.size < 1:
            raise self._reject(rid, "empty_prompt", "submit: empty prompt")
        if max_new < 1:
            raise self._reject(rid, "bad_max_new",
                               f"submit: max_new must be >= 1, "
                               f"got {max_new}")
        if tier not in VALID_TIERS:
            raise self._reject(rid, "bad_tier",
                               f"submit: tier must be one of "
                               f"{VALID_TIERS}, got {tier!r}")
        if prompt.size + max_new > self.cache_len:
            raise self._reject(
                rid, "exceeds_cache",
                f"submit: {prompt.size} prompt + {max_new} new tokens "
                f"exceed cache_len {self.cache_len}")
        need = -(-(prompt.size + max_new) // self.block_size)
        if need > self.num_blocks:
            # _admit reserves the worst-case block count up front; a
            # request needing more blocks than the pool HAS could never
            # reserve and would livelock the FIFO queue head forever
            raise self._reject(
                rid, "exceeds_pool",
                f"submit: {prompt.size} prompt + {max_new} new tokens "
                f"need {need} blocks, exceeding the pool's "
                f"{self.num_blocks}")
        budget = self.tenant_budgets.get(str(tenant))
        if budget is not None and prompt.size + max_new > budget:
            # same never-admittable class for budgets: a request whose
            # OWN charge exceeds its tenant's cap could never pass
            # _budget_ok even with nothing in flight — it would queue
            # forever (budget exhaustion queues; impossibility rejects)
            raise self._reject(
                rid, "exceeds_budget",
                f"submit: {prompt.size} prompt + {max_new} new tokens "
                f"exceed tenant {tenant!r}'s budget of {budget}")
        req = EngineRequest(
            rid=rid, prompt=prompt, max_new=int(max_new),
            temperature=float(temperature), top_k=int(top_k),
            eos_id=eos_id, tenant=str(tenant), tier=str(tier),
            submit_t=time.perf_counter(),
            trace_id=str(trace) if trace
            else f"eng{self._engine_id}.r{rid}")
        self._queue.append(req)
        self._m_requests.inc()
        self._m_queue.set(len(self._queue))
        # the request's trace track: async ``request`` slice + nested
        # ``queued`` slice
        self._ev(req, "request", "b", req.submit_t, rid=rid,
                 prompt_tokens=int(prompt.size), max_new=req.max_new,
                 tenant=req.tenant, tier=req.tier)
        self._ev(req, "queued", "b", req.submit_t)
        return req

    def abort_requests(self, reason: str = "replica_killed") -> int:
        """Close every live request's open trace slices (``queued`` /
        ``prefill`` / ``decode`` / ``request``) with an ``aborted``
        marker and drop the work. This is the IN-PROCESS analogue of
        the replica process dying: a real SIGKILL takes its span buffer
        with it (the merged fleet trace simply never sees the dead
        attempt), but an in-process fleet shares one buffer, so a kill
        simulation must close what the dead attempt opened or the
        joined trace shows unbalanced slices. The decode step in
        flight is read back first (requests it finishes end normally
        and the next ``step()`` returns them); every aborted slot then
        gives back its blocks and reservation, so no block leaks."""
        self._resolve()
        now = time.perf_counter()
        aborted: List[EngineRequest] = []
        # a preempted-to-blocks request closed its prefill/decode slices
        # at preemption and waits under a fresh "queued" one, like the
        # arrival queue's
        for req in itertools.chain(self._queue, self._preempted):
            self._ev(req, "queued", "e", now)
            aborted.append(req)
        for slot, req in enumerate(self._slot_req):
            if req is None:
                continue
            if req.first_token_t is None:
                self._ev(req, "prefill", "e", now)
            if req.decode_open:
                self._ev(req, "decode", "e", now)
                req.decode_open = False
            if slot in self._prefilling:
                self._prefilling.remove(slot)
            self._release_slot(slot, req)
            aborted.append(req)
        for req in aborted:
            req.status, req.finish_reason = "aborted", reason
            self._ev(req, "aborted", "n", now, reason=reason)
            self._ev(req, "request", "e", now)
        self._queue.clear()
        self._preempted.clear()
        self._m_queue.set(0)
        return len(aborted)

    @property
    def active_count(self) -> int:
        return int(self._active.sum())

    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    @property
    def free_slots(self) -> int:
        return len(self._free)

    @property
    def preempted_count(self) -> int:
        """Preempted requests parked awaiting resume."""
        return len(self._preempted)

    @property
    def idle(self) -> bool:
        """Nothing queued, prefilling, decoding, in flight on the
        device, or finished and not yet returned: a loop that steps
        until idle (``run_until_idle``, ``EngineLoop``'s drain and EOF
        exit) therefore reads the last decode step back."""
        return (self._inflight is None and not self._landed
                and not self._queue and not self._preempted
                and not self._prefilling and not self._active.any())

    # -- P/D disaggregation (KV transfer over the fleet wire) -------------
    def prefix_digests(self, prompt) -> List[bytes]:
        """Content-chain digests of ``prompt``'s TRANSFERABLE prefix:
        the chunk-aligned full blocks admission can serve as cache hits
        (the final chunk always recomputes locally — it must produce
        logits to sample from). This is the P/D transfer unit and the
        router's placement key."""
        from paddle_tpu.serving import blocks as _blocks
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        per = self.chunk_tokens // self.block_size
        usable = ((int(prompt.size) - 1) // self.chunk_tokens) * per
        if usable <= 0 or self.recurrent:   # nothing transferable
            return []
        return _blocks.prompt_block_hashes(prompt,
                                           self.block_size)[:usable]

    def export_prefix(self, prompt, trace: Optional[str] = None,
                      partial: bool = False) -> Optional[bytes]:
        """Serialize ``prompt``'s transferable prefix out of this pool
        — the prefill half of P/D disaggregation. Every prefix block
        must already be published (run the prompt through the scheduler
        first, e.g. ``submit(prompt, max_new=1)`` + drain: chunked
        prefill publishes the blocks as each chunk lands). Returns
        ``None`` when the prompt has no transferable prefix or any
        block was evicted before serialization — the receiver then
        falls back to a cold prefill, which is slower but identical.

        ``partial=True`` is the fleet cache-fetch mode: serve the
        LEADING chunk-aligned run from wherever it lives — HBM pool
        rows AND spilled DRAM/disk-tier payloads mixed in one chain —
        stopping at the first miss instead of returning None. The
        receiver cannot tell the sources apart (the spill format is
        the wire format), and a partial chain still serves hits there
        because admission stops at its first miss anyway. None only
        when the leading run is empty."""
        from paddle_tpu.serving import transfer as _transfer
        self._resolve()
        digests = self.prefix_digests(prompt)
        if not digests:
            return None
        names = None
        items = []
        for h in digests:
            b = self.pool.lookup(h)
            if b is not None:
                if names is None:
                    names = [n for n in _transfer.ARRAY_ORDER
                             if n in self.cache]
                items.append((h, {
                    n: np.asarray(_transfer._block_slab(
                        self.cache[n], int(b), self.block_size))
                    for n in names}))
                continue
            if not partial:
                return None
            got = self.tiers.get(h) if self.tiers is not None else None
            if got is None:
                break
            try:
                meta, sub = _transfer.deserialize_blocks(got[1])
                _transfer.check_pool_match(meta, self.cache,
                                           self.block_size,
                                           self.kv_dtype)
                if len(sub) != 1 or sub[0][0] != h:
                    raise ValueError("spill payload digest mismatch")
            except (ValueError, KeyError):
                self.tiers.quarantine(h)
                break
            items.append(sub[0])
        if not items:
            return None
        payload = _transfer.serialize_raw_blocks(
            _transfer.pool_meta(self.cache, self.block_size,
                                self.kv_dtype),
            items, trace=trace)
        self._m_kv_exported.inc(len(items))
        return payload

    def import_prefix(self, payload: bytes) -> int:
        """Adopt serialized prefix blocks into this pool via the
        ordinary prefix-cache publish path — the decode half of P/D
        disaggregation. Stamp-checked (pool layout / kv_dtype / slab
        shape must match this pool). Walks the chain in order, skipping
        digests already cached; stops early when the pool cannot
        reserve another block (a partial prefix still serves hits —
        admission stops at the first miss anyway). Returns the blocks
        newly adopted; they park refcount-0 in the LRU, hit-ready.
        Generation over adopted blocks is bitwise the colocated run
        (the PR-6 hit-vs-cold guarantee: identical KV bytes, identical
        chunk grid for the locally-computed tail)."""
        from paddle_tpu.serving import transfer as _transfer
        if self.recurrent:
            raise ValueError("import_prefix is off for a model with "
                             "recurrent state: pages are not all the "
                             "state a prefix leaves behind")
        meta, blocks = _transfer.deserialize_blocks(payload)
        _transfer.check_pool_match(meta, self.cache, self.block_size,
                                   self.kv_dtype)
        n = 0
        chain_blocks = set()    # pool blocks holding EARLIER digests
        #                         of this chain — cached before the
        #                         call or adopted by it
        pending = []
        for digest, arrays in blocks:
            existing = self.pool.lookup(digest)
            if existing is not None:
                chain_blocks.add(existing)
                continue
            if not self.pool.can_reserve(1):
                break
            if (self.pool.free_count == 0
                    and self.pool.lru_oldest() in chain_blocks):
                # the next alloc would evict one of THIS chain's own
                # leading blocks (already-cached head included): a
                # full-pool import must keep the leading run — a chain
                # with its head evicted serves zero hits (admission
                # stops at the first miss)
                break
            self.pool.reserve(1)
            b = self.pool.alloc()
            pending.append((b, arrays))
            self.pool.publish(digest, b)
            self.pool.release(b)    # refcount 0 + published: parks in
            chain_blocks.add(b)     # the LRU, served as a hit from here
            n += 1
        # value writes batched: one scatter per pool leaf for the whole
        # chain (nothing reads the pool between publish and here — the
        # engine is single-threaded)
        self.cache = _transfer.write_blocks(self.cache, pending,
                                            self.block_size)
        if n:
            self._m_kv_imported.inc(n)
        if meta.get("trace"):
            # the payload header carried the fleet trace context across
            # the P/D hop: mark the adoption on that track, so the
            # disaggregated prefill→decode handoff is one connected
            # timeline (the request's prefix_adopt hit follows at
            # admission)
            _chrome.record_event(
                "prefix_import", self._wall(time.perf_counter()), "n",
                str(meta["trace"]),
                args={"blocks": n, "chain": len(blocks)})
        return n

    # -- tiered spill (HBM -> host DRAM -> disk) ---------------------------
    def _demote_block(self, block: int, digest: bytes):
        """``pool.on_evict`` hook: serialize the LRU-evicted cached
        block with the transfer wire (the spill format IS the wire
        format) and park it in the DRAM/disk tiers. Fires inside
        ``alloc()`` BEFORE the new holder scatters over the rows, so
        the bytes still match the digest. Never raises into the
        allocation path — a failed spill is just a lost cache entry,
        exactly what eviction meant before tiers existed."""
        from paddle_tpu.serving import transfer as _transfer
        try:
            payload = _transfer.serialize_blocks(
                self.cache, [block], [digest], self.block_size,
                self.kv_dtype)
            self.tiers.put(digest, payload)
        except Exception:
            pass

    def _promote_for(self, req: EngineRequest):
        """Re-adopt ``req``'s spilled prefix from the DRAM/disk tiers
        into the pool at the moment admission is guaranteed, so the
        re-plan sees the promoted blocks as ordinary prefix-cache hits
        and the PR-6 bitwise hit-vs-cold contract carries across tiers
        unchanged.
        Walks the chain to the chunk-aligned hit cap and stops at the
        first full miss (a chain with a hole serves no hits past it).
        Runs ONCE per request (``tier_promote_done``); a corrupt or
        stamp-mismatched payload is quarantined and treated as the
        miss it is — never an exception on the admission path."""
        from paddle_tpu.serving import blocks as _blocks
        from paddle_tpu.serving import transfer as _transfer
        req.tier_promote_done = True
        bs = self.block_size
        hashes = req.block_hashes
        if hashes is None:
            hashes = _blocks.prompt_block_hashes(req.prompt, bs)
            req.block_hashes = hashes
        per = self.chunk_tokens // bs
        usable = ((int(req.prompt.size) - 1) // self.chunk_tokens) * per
        chain_blocks = set()
        pending = []
        promoted = 0
        for h in hashes[:usable]:
            existing = self.pool.lookup(h)
            if existing is not None:
                chain_blocks.add(existing)
                continue
            self._m_tier_miss.inc(tier="hbm")
            got = self.tiers.get(h)
            if got is None:
                self._m_tier_miss.inc(tier="dram")
                self._m_tier_miss.inc(tier="disk")
                break
            tier, payload = got
            if tier == "disk":
                self._m_tier_miss.inc(tier="dram")
            try:
                meta, items = _transfer.deserialize_blocks(payload)
                _transfer.check_pool_match(meta, self.cache, bs,
                                           self.kv_dtype)
                if len(items) != 1 or items[0][0] != h:
                    raise ValueError("spill payload digest mismatch")
            except (ValueError, KeyError):
                self.tiers.quarantine(h)
                break
            if not self.pool.can_reserve(1):
                break
            if (self.pool.free_count == 0
                    and self.pool.lru_oldest() in chain_blocks):
                # same guard as import_prefix: adopting one more block
                # must not evict this chain's own head
                break
            self.pool.reserve(1)
            b = self.pool.alloc()
            pending.append((b, items[0][1]))
            self.pool.publish(h, b)
            self.pool.release(b)        # refcount 0 + published: parks
            chain_blocks.add(b)         # in the LRU, hit-ready
            self._m_tier_hits.inc(tier=tier)
            promoted += 1
        self.cache = _transfer.write_blocks(self.cache, pending, bs)
        req.tier_promoted_blocks = promoted
        if promoted:
            self._m_kv_imported.inc(promoted)
            self._ev(req, "tier_promote", "n", time.perf_counter(),
                     blocks=promoted)

    # -- scheduler ---------------------------------------------------------
    def _seed(self) -> np.int32:
        return np.int32(self._rng.randint(0, 2 ** 31 - 1))

    def _alloc_page(self, slot: int):
        b = self.pool.alloc()
        self._pages[slot, self._nalloc[slot]] = b
        self._pages_dev = None
        self._nalloc[slot] += 1
        self._slot_blocks[slot].append(b)
        self._slot_reserved[slot] -= 1

    # -- multi-tenant admission / preemption -------------------------------
    def _charge(self, req: EngineRequest) -> int:
        """Worst-case tokens a live request holds against its tenant's
        budget — the same prompt+max_new the block reservation backs."""
        return int(req.prompt.size) + int(req.max_new)

    def _budget_ok(self, req: EngineRequest) -> bool:
        budget = self.tenant_budgets.get(req.tenant)
        if budget is None:
            return True
        return self._tenant_used.get(req.tenant, 0) \
            + self._charge(req) <= budget

    def _track_tenant(self, req: EngineRequest, delta: int):
        used = max(self._tenant_used.get(req.tenant, 0) + delta, 0)
        if used:
            self._tenant_used[req.tenant] = used
        else:
            # prune at zero: tenant names arrive unvalidated off the
            # JSONL wire, so keeping dead entries (or per-tenant gauge
            # samples) would grow host state one permanent row per
            # tenant name ever seen
            self._tenant_used.pop(req.tenant, None)
        if req.tenant in self.tenant_budgets:
            # gauge cardinality bounded by the CONFIGURED budget set,
            # not by whatever tenant strings clients invent
            self._m_tenant_tokens.set(used, tenant=req.tenant)

    def _charge_tenant(self, req: EngineRequest):
        self._track_tenant(req, self._charge(req))

    def _uncharge_tenant(self, req: EngineRequest):
        self._track_tenant(req, -self._charge(req))

    def _admission_plan(self, req: EngineRequest):
        """(hashes, hits, need, revive) for admitting ``req`` now."""
        from paddle_tpu.serving import blocks as _blocks
        bs = self.block_size
        Tp = req.prompt.size
        if self.recurrent:      # no lookup: every prompt prefills cold
            return [], [], -(-(Tp + req.max_new) // bs), 0
        hashes = req.block_hashes
        if hashes is None:      # computed once per request: the digests
            #                     are a pure function of the prompt, and
            #                     a reservation-blocked head re-enters
            #                     here every step
            hashes = _blocks.prompt_block_hashes(req.prompt, bs)
            req.block_hashes = hashes
        # cap hits CHUNK-aligned (not merely block-aligned): the
        # post-hit chunks must replay the cold prefill's exact chunk
        # grid for the bitwise hit-vs-cold guarantee, and at least the
        # last prompt token is always recomputed — the final chunk must
        # produce logits to sample from
        per = self.chunk_tokens // bs
        usable = ((Tp - 1) // self.chunk_tokens) * per
        hits: List[int] = []
        for h in hashes[:usable]:
            b = self.pool.lookup(h)
            if b is None:
                break
            hits.append(b)
        # a PARTIAL-chunk hit run must round DOWN to the chunk grid:
        # starting prefill mid-chunk would reach (bucket, span) shapes
        # off the exported grid — KeyError on v4 artifacts, extra
        # compiles in-process
        hits = hits[:len(hits) // per * per]
        need = -(-(Tp + req.max_new) // bs) - len(hits)
        # hits parked refcount-0 in the LRU are about to be revived by
        # share(): they leave the allocatable set, so the reservation
        # must clear them TOO or a later lazy alloc() could find the
        # pool exhausted despite its reservation
        revive = sum(1 for b in hits if self.pool.refcount(b) == 0)
        return hashes, hits, need, revive

    def _try_admit(self, req: EngineRequest,
                   finished: List[EngineRequest]) -> bool:
        """Admit ``req`` if a slot is free and its reservation fits;
        the plan is computed ONCE and handed to the admission body."""
        if not self._free:
            return False
        plan = self._admission_plan(req)
        _, _, need, revive = plan
        if not self.pool.can_reserve(need + revive):
            return False
        # promote ONLY once admission is certain: a promoted block
        # parks refcount-0 in the LRU, and a queued request's wait can
        # outlive that parking (other requests' allocs would evict the
        # promotion before it ever served a hit). Promotion keeps the
        # reservation check's ground truth intact — each promoted
        # block moves free -> LRU (allocatable unchanged) and its
        # digest moves need -> revive (the sum unchanged) — so the
        # can_reserve verdict above still stands; only the hit list
        # needs recomputing.
        if self.tiers is not None and not req.tier_promote_done:
            self._promote_for(req)
            if req.tier_promoted_blocks:
                plan = self._admission_plan(req)
        self._admit_request(req, finished, plan)
        return True

    def _admit_request(self, req: EngineRequest,
                       finished: List[EngineRequest], plan):
        """Place one admissible request into a slot (the PR-6 admission
        body). ``plan`` is the caller's ``_admission_plan`` result."""
        hashes, hits, need, revive = plan
        slot = self._free.popleft()
        self.pool.reserve(need)
        for b in hits:
            self.pool.share(b)
        self._pages[slot, :] = 0
        self._pages[slot, :len(hits)] = hits
        self._pages_dev = None
        self._nalloc[slot] = len(hits)
        self._slot_blocks[slot] = list(hits)
        self._slot_hashes[slot] = hashes
        self._slot_off[slot] = len(hits) * self.block_size
        self._slot_reserved[slot] = need
        self._slot_prefill_s[slot] = 0.0
        req.prefix_hit_tokens = len(hits) * self.block_size
        self._m_prefix_hits.inc(len(hits))
        # tier-labeled hit split: blocks _promote_for just re-adopted
        # were dram/disk hits (counted there); the rest were warm in
        # HBM all along
        hbm_hits = len(hits) - req.tier_promoted_blocks
        req.tier_promoted_blocks = 0
        if hbm_hits > 0:
            self._m_tier_hits.inc(hbm_hits, tier="hbm")
        # misses are counted as chunks actually run cold
        # (_prefill_chunk): a block published by a CONCURRENT
        # same-prefix request mid-prefill is adopted, not missed
        now = time.perf_counter()
        req.prefill_t = now
        if req.preemptions == 0:
            # re-admissions after a preemption would re-observe the
            # whole submit->now span on top of the first observation —
            # the histogram records each request's ORIGINAL queue wait
            self._m_wait_s.observe(now - req.submit_t)
        self._ev(req, "queued", "e", now)
        self._ev(req, "admitted", "n", now, slot=slot,
                 queue_wait_ms=round(1000 * (now - req.submit_t), 3),
                 hit_blocks=len(hits), reserved_blocks=need)
        self._ev(req, "prefill", "b", now)
        req.slot, req.status = slot, "prefilling"
        self._slot_req[slot] = req
        self._charge_tenant(req)
        if req.replay is not None:
            # preempt-resume eviction fallback: the prompt re-prefills
            # on its exact cold chunk grid (cache hits make surviving
            # chunks free), then the already-emitted history replays
            # through the decode program without re-emitting
            self._slot_forced[slot] = deque(req.replay)
            req.replay = None
        self._prefilling.append(slot)

    def _admit(self, finished: List[EngineRequest]):
        """Tiered, budget-aware admission. Priority classes, scanned in
        order each scheduler step:

        1. **latency-tier queue** (FIFO) — a reservation-blocked head
           may preempt batch-tier victims; while it stays blocked,
           nothing below it admits (strict priority).
        2. **preempted resumes** (oldest first) — ahead of fresh
           batch admissions so preemption is a delay, not a demotion.
        3. **batch-tier queue** (FIFO) — head-of-line on reservation,
           like the single-tenant engine.

        In every class a request whose TENANT budget is exhausted is
        SKIPPED, not blocked on: token budgets isolate tenants from
        each other, so one tenant's burst must not head-of-line-block
        the rest of the fleet. Budget exhaustion therefore queues
        (the request stays, admitted when its tenant's tokens free) —
        it never rejects."""
        blocked = False
        for req in [r for r in self._queue if r.tier == "latency"]:
            if not self._budget_ok(req):
                continue
            admitted = self._try_admit(req, finished)
            if not admitted and self._preemption_feasible(req):
                while not admitted and self._preempt_victim():
                    admitted = self._try_admit(req, finished)
            if not admitted:
                blocked = True
                break
            self._queue.remove(req)
        if not blocked:
            for req in list(self._preempted):
                if not self._budget_ok(req):
                    continue
                if self._try_resume(req, finished) is None:
                    blocked = True
                    break
                self._preempted.remove(req)
            if not blocked:
                for req in [r for r in self._queue
                            if r.tier == "batch"]:
                    if not self._budget_ok(req):
                        continue
                    if not self._try_admit(req, finished):
                        break
                    self._queue.remove(req)
        self._m_queue.set(len(self._queue) + len(self._preempted))

    def _preemption_feasible(self, req: EngineRequest) -> bool:
        """Could evicting batch-tier work EVER free enough for ``req``?
        Worst-case need vs everything not pinned by latency-tier
        holders. False stops a blocked latency request from pointlessly
        draining every batch victim it can never benefit from."""
        held_lat = sum(self._nalloc[s] + self._slot_reserved[s]
                       for s, r in enumerate(self._slot_req)
                       if r is not None and r.tier != "batch")
        need = -(-(req.prompt.size + req.max_new) // self.block_size)
        return need <= self.num_blocks - held_lat

    def _preempt_victim(self) -> bool:
        """Preempt ONE batch-tier victim to free blocks (and its slot)
        for a blocked latency-tier admission. Victim choice: the
        batch request holding the most pool resources (allocated +
        still-reserved blocks — what preemption actually frees); ties
        break toward the most recently admitted (least sunk prefill
        work). Returns False when no batch-tier work is preemptable."""
        best, best_key = -1, None
        for slot, req in enumerate(self._slot_req):
            if req is None or req.tier != "batch":
                continue
            if req.status not in ("prefilling", "running"):
                continue
            key = (self._nalloc[slot] + self._slot_reserved[slot],
                   req.prefill_t or 0.0)
            if best_key is None or key > best_key:
                best, best_key = slot, key
        if best < 0:
            return False
        self._preempt(best)
        return True

    def _preempt(self, slot: int):
        """Preempt-to-blocks: snapshot the slot's decode cursor, publish
        every fully-written block (prompt chain continued over the
        generated tokens, plus the partial tail block under its own
        chain digest) into the prefix cache, release the pages and the
        reservation. The pool makes this a pure host operation — no
        device copy moves — and resume is either a straight re-mapping
        (blocks survived in the LRU) or a cache-hit chunked prefill
        plus forced decode replay (blocks evicted). A victim still
        PREFILLING simply re-queues: its published chunks already sit
        in the prefix cache, so re-admission hits them. The decode step
        in flight is read back first, for an exact cursor; a victim it
        finishes has freed its slot already."""
        from paddle_tpu.serving import blocks as _blocks
        self._resolve()
        req = self._slot_req[slot]
        if req is None:
            return
        now = time.perf_counter()
        bs = self.block_size
        blocks = list(self._slot_blocks[slot])
        if req.status == "running" and self.recurrent:
            # nothing to publish and no cursor worth keeping: the
            # recurrent rows are lost with the slot, so resume replays
            # the prompt cold and the emitted tokens through decode
            if req.decode_open:
                self._ev(req, "decode", "e", now)
                req.decode_open = False
            req.snapshot = None
            published = 0
        elif req.status == "running":
            if req.decode_open:
                self._ev(req, "decode", "e", now)
                req.decode_open = False
            pos = int(self._pos[slot])
            seq = np.concatenate(
                [req.prompt, np.asarray(req.tokens, np.int32)])
            nfull = pos // bs
            hashes = _blocks.prompt_block_hashes(seq[:nfull * bs], bs)
            tail_len = pos % bs
            tail_hash = None
            if tail_len:
                parent = hashes[-1] if hashes else _blocks.ROOT_HASH
                tail_hash = _blocks.chain_hash(
                    parent, seq[nfull * bs:pos])
            for j, h in enumerate(hashes):
                self.pool.publish(h, blocks[j])
            if tail_hash is not None:
                self.pool.publish(tail_hash, blocks[nfull])
            req.snapshot = {
                "hashes": hashes, "tail_hash": tail_hash,
                "tail_len": tail_len, "pos": pos,
                "last": int(self._last[slot]),
                "forced": list(self._slot_forced[slot])}
            published = nfull + (1 if tail_len else 0)
        else:                       # mid-prefill: published chunk
            published = 0           # blocks already carry their hashes
            self._prefilling.remove(slot)
            self._ev(req, "prefill", "e", now)   # close the open slice
            if self._slot_forced[slot]:
                # a replay-resuming victim preempted AGAIN mid-prefill:
                # its un-replayed history must survive the re-queue or
                # the next admission would RE-EMIT already-delivered
                # tokens (replay restarts from the full emitted list —
                # the prompt prefill re-derives the earlier part)
                req.replay = list(req.tokens)
        self._release_slot(slot, req)
        req.slot = -1
        req.preemptions += 1
        self._m_preempts.inc()
        self._ev(req, "preempted", "n", now, tokens=len(req.tokens),
                 blocks_published=published, was=req.status)
        # the request is queued again (the resume line or the arrival
        # queue): open a fresh "queued" slice so the re-admission's
        # (or remap-resume's) "queued e" stays balanced
        self._ev(req, "queued", "b", now)
        if req.status == "running":
            req.status = "preempted"
            self._preempted.append(req)
        else:
            req.status = "queued"
            self._queue.appendleft(req)

    def _try_resume(self, req: EngineRequest,
                    finished: List[EngineRequest]) -> Optional[str]:
        """Resume one preempted request. Fast path (``"remap"``): every
        snapshot digest still resolves in the prefix cache — share the
        blocks back into a fresh page table, un-publish the partial
        tail (decode writes into it again), restore the cursor; no
        device work at all, and generation continues bitwise as if
        never preempted. Eviction fallback (``"replay"``): re-admit
        through the normal chunked prefill (the prompt's surviving
        chunks are cache hits on the exact cold grid) and force-feed
        the already-emitted tokens through the decode program — same
        program shapes as the original run, so the continuation stays
        bitwise too. ``None``: blocked on a slot or reservation."""
        from paddle_tpu.serving import blocks as _blocks
        if not self._free:
            return None
        snap = req.snapshot
        bs = self.block_size
        blocks: List[int] = []
        ok = snap is not None       # None: recurrent state, replay only
        for h in (snap["hashes"] if ok else ()):
            b = self.pool.lookup(h)
            if b is None:
                ok = False
                break
            blocks.append(b)
        tail_b = None
        if ok and snap["tail_hash"] is not None:
            tail_b = self.pool.lookup(snap["tail_hash"])
            # the tail block gets WRITTEN into: it must be exclusively
            # ours (refcount-0, LRU-parked); anything else falls back
            # to replay rather than corrupting a shared block
            if tail_b is None or self.pool.refcount(tail_b) != 0:
                ok = False
            else:
                blocks.append(tail_b)
        if ok:
            need = -(-(req.prompt.size + req.max_new) // bs) \
                - len(blocks)
            revive = sum(1 for b in blocks
                         if self.pool.refcount(b) == 0)
            if not self.pool.can_reserve(need + revive):
                return None
            now = time.perf_counter()
            slot = self._free.popleft()
            self.pool.reserve(need)
            for b in blocks:
                self.pool.share(b)
            if tail_b is not None:
                self.pool.unpublish(tail_b)
            self._pages[slot, :] = 0
            self._pages[slot, :len(blocks)] = blocks
            self._pages_dev = None
            self._nalloc[slot] = len(blocks)
            self._slot_blocks[slot] = list(blocks)
            self._slot_hashes[slot] = req.block_hashes or \
                _blocks.prompt_block_hashes(req.prompt, bs)
            self._slot_off[slot] = req.prompt.size
            self._slot_reserved[slot] = need
            self._slot_forced[slot] = deque(snap.get("forced", ()))
            req.slot, req.status = slot, "running"
            self._slot_req[slot] = req
            self._activate(slot, req, snap["pos"], snap["last"])
            self._charge_tenant(req)
            req.snapshot = None
            self._ev(req, "queued", "e", now)
            if not req.decode_open:
                self._ev(req, "decode", "b", now)
                req.decode_open = True
            self._m_resumes.inc(mode="remap")
            self._ev(req, "resumed", "n", now, mode="remap",
                     blocks=len(blocks))
            return "remap"
        # eviction fallback: forced replay through normal admission
        req.replay = list(req.tokens)
        if not self._try_admit(req, finished):
            req.replay = None           # still parked: keep the
            return None                 # snapshot for the next attempt
        req.snapshot = None
        self._m_resumes.inc(mode="replay")
        self._ev(req, "resumed", "n", time.perf_counter(),
                 mode="replay", replay_tokens=len(req.tokens))
        return "replay"

    def _draft_chunk_hook(self, slot: int, padded, c: int, npages: int):
        """No-op on the plain paged engine; the spec engine mirrors the
        chunk into the draft pool here."""

    def _try_adopt(self, slot: int) -> bool:
        """Map the slot's NEXT chunk straight onto cached blocks when
        every block of it is already published — a CONCURRENT
        same-prefix request cold-prefilled it after this one was
        admitted. Shares the blocks, returns the reservation, skips the
        chunk program entirely. Only whole chunk-aligned chunks below
        the hit cap qualify, so the hit-vs-cold bitwise guarantee's
        chunk grid is preserved."""
        req = self._slot_req[slot]
        off = self._slot_off[slot]
        bs, K = self.block_size, self.chunk_tokens
        cap = ((req.prompt.size - 1) // K) * K
        if off % K or off >= cap or self.recurrent:
            return False
        hashes = self._slot_hashes[slot]
        first = off // bs
        blocks = []
        for j in range(first, first + K // bs):
            b = self.pool.lookup(hashes[j])
            if b is None:
                return False
            blocks.append(b)
        for b in blocks:
            self.pool.share(b)
            self._pages[slot, self._nalloc[slot]] = b
            self._nalloc[slot] += 1
            self._slot_blocks[slot].append(b)
        self._pages_dev = None
        self.pool.unreserve(len(blocks))
        self._slot_reserved[slot] -= len(blocks)
        self._slot_off[slot] = off + K
        req.prefix_hit_tokens += K
        self._m_prefix_hits.inc(len(blocks))
        self._m_tier_hits.inc(len(blocks), tier="hbm")
        self._ev(req, "prefix_adopt", "n", time.perf_counter(),
                 hit_blocks=len(blocks), tokens=K)
        return True

    def _dispatch_chunk(self, slot: int, padded, c: int, npages: int,
                        temperature, top_k, seed):
        """One chunk-prefill program over ``slot``'s first ``npages``
        pages; returns the sampled first token (still on device)."""
        jnp = self._jnp
        # a recurrent model's chunk also names the slot whose rows it
        # reads and writes
        whose = (np.int32(slot),) if self.recurrent else ()
        tok, self.cache = self._tracker.track_call(
            "serving_engine.prefill", self._prefill_fn,
            self.params, self.cache, jnp.asarray(padded),
            np.int32(c), jnp.asarray(self._pages[slot, :npages]), *whose,
            np.float32(temperature), np.int32(top_k), seed)
        # the spec engine's draft model prefills the SAME chunk into
        # its own pool here (same page vector — one block table maps
        # both pools, so hits/preemption/eviction stay in lockstep)
        self._draft_chunk_hook(slot, padded, c, npages)
        return tok

    def _prefill_chunk(self, finished: List[EngineRequest]):
        from paddle_tpu.core import ragged
        with self.phase("schedule"):
            slot = self._prefilling.popleft()
            req = self._slot_req[slot]
            while self._try_adopt(slot):
                pass
            off = self._slot_off[slot]
            c = min(req.prompt.size - off, self.chunk_tokens)
            bucket = ragged.bucket_length(c, self.buckets)
            end_page = -(-(off + c) // self.block_size)
            while self._nalloc[slot] < end_page:
                self._alloc_page(slot)
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :c] = req.prompt[off:off + c]
            # the page-vector PREFIX covering context + chunk: its length
            # (off/bs context pages + the bucket's own span) is what
            # makes the chunk program span-specialized — a cold chunk
            # attends over C tokens, not cache_len. Entries past the
            # allocated count back only padding positions, whose writes
            # drop.
            npages = off // self.block_size \
                + -(-bucket // self.block_size)
            stalled = bool(self._active.any())
        with self.phase("prefill_chunk", tokens=int(c),
                        bucket=bucket) as chunk:
            tok = self._dispatch_chunk(
                slot, padded, c, npages, req.temperature, req.top_k,
                self._seed())
            if self._inflight is not None:
                # the decode step queued ahead of the chunk: its time
                # is this prompt's stall, not its own prefill
                self._inflight.ids.block_until_ready()
            own_t0 = time.perf_counter()
            tok = np.asarray(tok)
            if tok.ndim:                # [token, 3 counts]
                self._moe_counters()[0].inc(int(tok[1]))
                tok = tok[0]
            tok = int(tok)
        with self.phase("schedule"):
            self._chunk_done(slot, req, off, c, tok, own_t0, chunk.end,
                             stalled, finished)

    def _chunk_done(self, slot: int, req: EngineRequest, off: int, c: int,
                    tok: int, own_t0: float, now: float, stalled: bool,
                    finished: List[EngineRequest]):
        """Bookkeeping after one chunk program (its own device time
        from ``own_t0`` to ``now``): publish its blocks, and on the
        prompt's final chunk hand the slot to decode."""
        # accumulate per-chunk device time; the histogram observes one
        # per-request total at the final chunk (chunk-grain timing
        # lives in engine_prefill_chunk_seconds and the stall
        # histogram)
        self._slot_prefill_s[slot] += now - own_t0
        self._m_chunks.inc()
        if stalled:
            self._m_stall.observe(now - own_t0)
        # publish the chunk's fully-written prompt blocks NOW (not at
        # prompt completion): a concurrent same-prefix request adopts
        # them instead of re-prefilling — a burst of shared-prefix
        # arrivals cold-prefills the prefix exactly once
        cold = 0
        for j in range(off // self.block_size,
                       0 if self.recurrent
                       else (off + c) // self.block_size):
            self.pool.publish(self._slot_hashes[slot][j],
                              int(self._pages[slot, j]))
            self._m_prefix_miss.inc()
            cold += 1
        self._ev(req, "prefill_chunk", "n", now, tokens=int(c),
                 cold_blocks=cold,
                 hit_blocks=req.prefix_hit_tokens // self.block_size,
                 stalled_decoders=int(self._active.sum()) if stalled
                 else 0)
        self._slot_off[slot] = off + c
        if off + c < req.prompt.size:
            self._prefilling.append(slot)   # round-robin: one chunk per
            return                          # step, decode in between
        # final chunk: emit the sampled first token
        req.prefill_own_s = self._slot_prefill_s[slot]
        self._m_prefill_s.observe(self._slot_prefill_s[slot])
        self._m_prefills.inc()
        req.status = "running"
        if self._slot_forced[slot]:
            # preempt-resume replay: this prompt's first token was
            # emitted before the preemption — the chunk grid just
            # re-derived it (bitwise under greedy; forced regardless,
            # so sampled histories replay exactly too). Restore the
            # decode cursor, re-emit nothing; the lifecycle slices
            # still transition (prefill closes, decode reopens) so the
            # trace stays b/e-balanced through a replay.
            self._ev(req, "prefill", "e", now)
            if not req.decode_open:
                self._ev(req, "decode", "b", now)
                req.decode_open = True
            self._activate(slot, req, req.prompt.size,
                           self._slot_forced[slot].popleft())
            return
        if self._emit(req, tok, now):
            finished.append(req)            # blocks released by _finish;
            return                          # published ones park in LRU
        self._activate(slot, req, req.prompt.size, tok)

    def _activate(self, slot: int, req: EngineRequest, pos: int,
                  last: int):
        """Hand ``slot`` to decode at ``pos``, its next input ``last``
        known to the host."""
        self._active[slot] = True
        self._pos[slot] = pos
        self._last[slot] = last
        self._temp[slot] = req.temperature
        self._topk[slot] = req.top_k

    def _release_slot(self, slot: int, req: EngineRequest):
        """Give ``slot`` back with everything it holds: its blocks, the
        unallocated rest of its reservation, its tenant charge."""
        for b in self._slot_blocks[slot]:
            self.pool.release(b)
        self.pool.unreserve(self._slot_reserved[slot])
        self._slot_blocks[slot] = []
        self._slot_hashes[slot] = []
        self._slot_reserved[slot] = 0
        self._nalloc[slot] = 0
        self._slot_off[slot] = 0
        self._slot_forced[slot] = deque()
        self._pages[slot, :] = 0
        self._pages_dev = None
        self._active[slot] = False
        self._last[slot] = 0
        self._slot_req[slot] = None
        self._free.append(slot)
        self._uncharge_tenant(req)

    def _finish(self, req: EngineRequest, reason: str, now: float):
        slot = req.slot
        if slot >= 0:
            self._release_slot(slot, req)
        req.status, req.finish_reason, req.finish_t = "done", reason, now
        self._m_completed.inc(reason=reason)
        if req.latency_s and req.latency_s > 0:
            goodput = len(req.tokens) / req.latency_s
            self._m_goodput.observe(goodput)
            self._win_tps.observe(goodput)
        if req.decode_open:
            self._ev(req, "decode", "e", now)
            req.decode_open = False
        self._ev(req, "finished", "n", now, reason=reason,
                 tokens=len(req.tokens))
        self._ev(req, "request", "e", now)
        self._record_request(req)
        self._update_window_gauges()

    def _emit(self, req: EngineRequest, tok: int, now: float) -> bool:
        """Record one emitted token; True when the request finished."""
        req.tokens.append(int(tok))
        self._m_tokens.inc()
        finishing = ((req.eos_id is not None and tok == req.eos_id)
                     or len(req.tokens) >= req.max_new)
        if req.first_token_t is None:
            req.first_token_t = now
            ttft = now - req.submit_t
            self._m_ttft_s.observe(ttft)
            self._win_ttft.observe(ttft)
            self._tier_window(req.tier).observe(ttft)
            self._ev(req, "prefill", "e", now)
            self._ev(req, "first_token", "n", now,
                     ttft_ms=round(1000 * ttft, 3))
            if not finishing:
                self._ev(req, "decode", "b", now)
                req.decode_open = True
        if req.eos_id is not None and tok == req.eos_id:
            self._finish(req, "eos", now)
            return True
        if len(req.tokens) >= req.max_new:
            self._finish(req, "max_tokens", now)
            return True
        return False

    def _schedule(self, finished: List[EngineRequest]):
        """Admission and prefill-chunk work, ahead of the decode
        step."""
        with self.phase("schedule"):
            self._admit(finished)
        # With decoders in flight, at most ONE chunk runs per step —
        # the stall a prefill inflicts on them is bounded by a single
        # chunk program. With NOTHING decoding there is nobody to
        # stall: drain chunks back-to-back (a burst of arrivals reaches
        # its first tokens as fast as the chunk programs run) until a
        # finished prompt activates a decoder.
        while self._prefilling:
            self._prefill_chunk(finished)
            if finished:
                with self.phase("schedule"):
                    self._admit(finished)   # a one-token request freed
                    #                         its slot mid-schedule
            if self._active.any():
                break

    def _pre_decode(self):
        """Host bookkeeping a decode step needs first."""
        # lazily allocate the page each active row is about to write
        # (reservation at admission guarantees this never fails)
        for slot in np.flatnonzero(self._active):
            if self._pos[slot] // self.block_size >= self._nalloc[slot]:
                self._alloc_page(slot)

    def _decode_extra(self):
        """The decode programs' page-table argument, uploaded when it
        changed."""
        if self._pages_dev is None:
            self._pages_dev = self._upload(self._pages)
        return (self._pages_dev,)

    @property
    def moe_stats(self) -> bool:
        """Whether the step programs append the expert layer's three
        counts to the ids they return
        (``sampling._skeleton_paged_step_fns``): read off their results,
        so known from ``precompile()`` or the first step on."""
        return self._m_moe is not None

    def _moe_counters(self):
        if self._m_moe is None:
            reg = self.metrics
            self._m_moe = (
                reg.counter(
                    "engine_moe_assignments_total", "token-to-expert "
                    "assignments computed on this chip (those whose "
                    "expert it holds), prefill and decode"),
                reg.counter(
                    "engine_moe_decode_experts_hit_total", "distinct "
                    "held experts hit, summed over the expert-layer "
                    "calls of decode steps"),
                reg.counter(
                    "engine_moe_decode_layer_calls_total", "expert-layer "
                    "calls made by decode steps"))
        return self._m_moe

    def step(self) -> List[EngineRequest]:
        """One scheduler iteration: admit waiting requests, run the
        prefill chunks due (one while anything decodes), dispatch one
        batched decode step for everything in flight, then read back
        the decode step the call before dispatched and emit its tokens.
        Returns the requests that finished during this step."""
        finished = self._landed
        self._open_step()
        if not self._active.any():
            # nothing to queue behind the step in flight: read it back
            # first (admission then sees the slots it freed), and the
            # time until the next decode step is not a step's
            self._resolve()
            self._step_end = None
        self._schedule(finished)
        if self._active.any():
            prev, self._inflight = self._inflight, None
            with self.phase("decode_stage") as stage:
                self._pre_decode()
                staged = self._stage_decode(
                    self._seed(), None if prev is None else prev.ids)
            with self.phase("decode_dispatch") as dispatch:
                ids = self._call_decode(*staged)
                self._inflight = self._advance(ids, (stage, dispatch),
                                               prev)
            if prev is not None:
                self._land(prev)
        self._landed = []
        self._close_step()
        return finished

    def _advance(self, ids, phases, prev: Optional[_InFlight]
                 ) -> _InFlight:
        """Move the cursors past the decode step just dispatched, before
        its ids are known: each row's position advances; a replay row
        takes its next forced token; any other row's next input is this
        step's id (``_FROM_DEVICE``), and a row whose request reaches
        ``max_new`` with it leaves the batch (the slot stays its
        request's until the read-back finishes it)."""
        ahead = {} if prev is None else \
            {slot: req for slot, req, emits in prev.rows if emits}
        live = self._pos[self._active].astype(np.int64) + 1
        rows = []
        for slot in np.flatnonzero(self._active):
            req = self._slot_req[slot]
            self._pos[slot] += 1
            forced = self._slot_forced[slot]
            if forced:
                # replay after a preempt-to-blocks resume: the step runs
                # at the right (pos, last) and its pool write is what
                # matters; its id re-derives the known next token
                # (bitwise under greedy), which advances the cursor
                # WITHOUT re-emitting — the caller holds it
                self._last[slot] = forced.popleft()
                rows.append((slot, req, False))
                continue
            self._last[slot] = _FROM_DEVICE
            rows.append((slot, req, True))
            owed = 2 if ahead.get(slot) is req else 1
            if len(req.tokens) + owed >= req.max_new:
                self._active[slot] = False
        return _InFlight(ids, rows, live, phases, prev is not None)

    def _land(self, rec: _InFlight) -> np.ndarray:
        """Read one dispatched decode step back and emit its tokens; a
        row whose request finished meanwhile (on EOS, at the read-back
        before) is discarded. Returns the ids."""
        with self.phase("decode_sync") as sync:
            # the only device->host transfer: [B] int32 ids (and,
            # after them, an expert layer's three counts)
            ids = np.asarray(rec.ids)
        now = self._close_decode(rec.phases + (sync,))
        if rec.overlapped:
            self._m_overlapped.inc()
        with self.phase("emit"):
            if ids.size > self.batch:
                for m, n in zip(self._moe_counters(), ids[self.batch:]):
                    m.inc(int(n))
            self._count_decode_rows(rec.live)
            for slot, req, emits in rec.rows:
                if emits and self._slot_req[slot] is req \
                        and self._emit(req, int(ids[slot]), now):
                    self._landed.append(req)
        return ids

    def _resolve(self):
        """Read back the decode step in flight, if any, so that the
        host's state is exact: the cursors, each row's next input, and
        the requests it finished (the next ``step()`` returns them).
        Called wherever exact state is needed (``_preempt``,
        ``export_prefix``, ``abort_requests``) and by ``step()`` when
        no row is left to decode."""
        rec, self._inflight = self._inflight, None
        if rec is None:
            return
        ids = self._land(rec)[:self.batch]
        held = self._last == _FROM_DEVICE
        self._last[held] = ids[held]

    def _count_decode_rows(self, live: np.ndarray):
        """A decode step read back: rows its attention had to read
        (``live``, from the positions at dispatch) against rows it
        read."""
        from paddle_tpu.ops.pallas.policy import PATH_XLA
        self._m_live_rows.inc(int(live.sum()))
        if self.kernel_paths.get("decode", {}).get(
                "attention", PATH_XLA) == PATH_XLA:
            self._m_read_rows.inc(self.batch * self.cache_len)
        else:
            bs = self.block_size
            self._m_read_rows.inc(int((-(-live // bs) * bs).sum()))

    def _open_step(self):
        self._tag = {"step": int(self._m_steps.value()),
                     "active": self.active_count}

    def _close_decode(self, phases) -> float:
        """Account one completed decode step from its stage, dispatch
        and sync phases; returns its completion time."""
        now = phases[-1].end
        self._m_step_s.observe(sum(p.end - p.t0 for p in phases))
        self._m_steps.inc()
        if self._step_end is not None \
                and now - self._step_end > _SLOW_STEP_S:
            self._m_slow_steps.inc()
        self._step_end = now
        return now

    def _close_step(self):
        if not self._active.any() and self._inflight is None:
            self._step_end = None       # no decoder in flight: the next
            #                             interval is not a step's
        self._m_occupancy.set(self.active_count)
        pool = self.pool
        self._m_blocks_in_use.set(pool.in_use)
        self._m_blocks_free.set(pool.free_count)
        self._m_blocks_cached.set(pool.cached_free_count)
        if pool.evictions > self._evictions_seen:
            self._m_evictions.inc(pool.evictions - self._evictions_seen)
            self._evictions_seen = pool.evictions

    def _upload(self, host: np.ndarray):
        """A host vector put on the device through a copy of its own: a
        device array may read its numpy source when the program runs
        (the CPU aliases it), and the host moves these vectors on while
        the step that read them is still queued."""
        return self._jnp.asarray(host.copy())

    def _stage_decode(self, seed, ids=None):
        """The decode program's arguments over the current slot state
        (the [B] vectors and the page table uploaded; ``ids``, the step
        in flight's, fill the inputs ``_FROM_DEVICE`` marks) and their
        compile-tracker signature."""
        last = self._upload(self._last)
        if ids is not None:
            last = self._next_inputs(ids, last)
        args = (self.params, self.cache, last,
                self._upload(self._pos), self._upload(self._active),
                *self._decode_extra(),
                self._upload(self._temp), self._upload(self._topk), seed)
        return args, _ct.arg_signature(args, {})

    def _call_decode(self, args, sig):
        """One batched decode program over staged arguments; returns
        the sampled ids (still on device)."""
        nxt, self.cache = self._tracker.call_signed(
            "serving_engine.decode", sig, self._decode_fn, *args)
        return nxt

    def precompile(self) -> Dict[str, int]:
        """Run every program this engine can dispatch once, on inputs
        that change nothing a request will read (no active row;
        zero-length chunks), so each is compiled — and has executed on
        the device — before the engine reports
        ready. A kernel the compiler refuses raises HERE, with the
        compiler's message, instead of in the middle of traffic; later
        dispatches hit the jit cache. Only valid on an idle engine.
        Returns :meth:`compile_counts`."""
        if not self.idle:
            raise RuntimeError("precompile() needs an idle engine")
        with _trace.trace_scope("precompile/prefill"):
            # every (chunk bucket, context span) program of the chunk
            # grid, each on a zero-length chunk: its span write is
            # fully masked, so the pool keeps its bytes
            bs = self.block_size
            for ctx in range(0, self.cache_len, self.chunk_tokens):
                for b in self.buckets:
                    np.asarray(self._dispatch_chunk(
                        0, np.zeros((1, b), np.int32), 0,
                        ctx // bs + -(-b // bs), 0.0, 0, np.int32(0)))
        with _trace.trace_scope("precompile/decode"):
            self._precompile_decode()     # ends in a host read: all done
        return self.compile_counts()

    def _precompile_decode(self):
        # no active row: every cache write of the step is dropped; the
        # next step's input select compiles on its ids
        ids = self._call_decode(*self._stage_decode(np.int32(0)))
        np.asarray(self._next_inputs(ids, self._upload(self._last)))
        if ids.size > self.batch:   # counts after the ids, none counted
            self._moe_counters()

    def run_until_idle(self, max_steps: int = 100_000
                       ) -> List[EngineRequest]:
        """Drive ``step()`` until queue and slots drain; returns every
        request finished along the way (submission order not guaranteed
        — requests terminate independently)."""
        done: List[EngineRequest] = []
        for _ in range(max_steps):
            if self.idle:
                return done
            done.extend(self.step())
        raise RuntimeError(f"engine did not drain in {max_steps} steps "
                           f"({self.queue_depth} queued, "
                           f"{self.active_count} active)")

    # -- observability -----------------------------------------------------
    def decode_mfu(self) -> Optional[float]:
        """Mean decode-step MFU over this engine's lifetime: decode
        FLOPs / (mean step seconds × chip peak). None until a step ran
        or when FLOPs/peak are unknown — the figure ``serving_bench``
        reports (XLA's cost model over host time: no share of a chip)."""
        cell = self._m_step_s._peek({})
        if cell is None or not cell.count:
            return None
        return _costs.mfu(self.decode_flops, cell.sum / cell.count,
                          self._peak_flops)

    def health(self) -> dict:
        doc = {"requests": int(self._m_requests.value()),
               "completed": sum(
                   int(self._m_completed.value(reason=r))
                   for r in ("eos", "max_tokens")),
               "tokens": int(self._m_tokens.value()),
               "decode_steps": int(self._m_steps.value()),
               "queue_depth": self.queue_depth,
               "slots_active": self.active_count,
               "slots_total": self.batch,
               "cache_len": self.cache_len,
               "pallas": self.pallas_mode,
               "kernel_paths": self.kernel_paths,
               "prefill_buckets": list(self.buckets),
               "block_size": self.block_size,
               "blocks_total": self.num_blocks,
               "blocks_in_use": self.pool.in_use,
               "blocks_cached": self.pool.cached_free_count,
               "prefix_cache_entries": self.pool.cached_count,
               "chunk_tokens": self.chunk_tokens,
               "kv_dtype": self.kv_dtype,
               "kv_bytes_per_token": self.kv_bytes_per_token,
               "pool_bytes": self.pool_bytes,
               "recurrent_state_bytes": self.recurrent_state_bytes,
               "preempted_queued": len(self._preempted),
               "preemptions": int(self._m_preempts.value())}
        self._update_window_gauges()
        # per-token decode FLOPs: the recompute cost the fleet router's
        # fetch-vs-recompute crossover weighs against kv_bytes_per_token
        if self.decode_flops:
            doc["flops_per_token"] = float(self.decode_flops) \
                / max(self.batch, 1)
        # tier section: occupancy + a capped newest-first digest listing
        # per tier (hbm included) — what the router scrapes into its
        # fleet-global cache directory. Present even without a spill
        # store so an HBM-only replica still advertises its warm set.
        tiers_doc = (self.tiers.health() if self.tiers is not None
                     else {"digests": {}})
        tiers_doc["digests"]["hbm"] = [
            d.hex() for d in self.pool.cached_digests(512)]
        doc["tiers"] = tiers_doc
        tenants = sorted(set(self._tenant_used)
                         | set(self.tenant_budgets))
        if tenants:
            doc["tenants"] = {
                t: {"tokens_in_flight": self._tenant_used.get(t, 0),
                    "budget": self.tenant_budgets.get(t)}
                for t in tenants}
        ttft = self._win_ttft.quantiles((0.5, 0.95, 0.99))
        doc["window"] = {
            "window_s": self._win_ttft.window_s,
            "requests": self._win_ttft.count(),
            "ttft_p50_s": round(ttft[0.5], 6),
            "ttft_p95_s": round(ttft[0.95], 6),
            "ttft_p99_s": round(ttft[0.99], 6),
            "tokens_per_sec_p50": round(self._win_tps.quantile(0.5), 3),
            # raw windowed TTFT samples in clock-free [age_s, value]
            # form (newest 512): the fleet aggregator POOLS these for
            # its fleet quantiles — per-replica quantiles cannot be
            # averaged (see WindowedQuantiles.samples)
            "ttft_samples": [[round(a, 4), round(v, 6)] for a, v in
                             self._win_ttft.export_samples()[-512:]]}
        if self._win_ttft_tier:
            doc["window"]["tiers"] = {
                tier: {"requests": win.count(),
                       "ttft_p50_s": round(win.quantile(0.5), 6),
                       "ttft_p99_s": round(win.quantile(0.99), 6)}
                for tier, win in sorted(self._win_ttft_tier.items())}
        if self.slo is not None:
            burn = self._slo_burn_rate()
            doc["slo"] = {"ttft_s": self.slo.ttft_s,
                          "target": self.slo.target,
                          "window_s": self.slo.window_s,
                          "burn_threshold": self.slo.burn_threshold,
                          "ttft_burn_rate": round(burn, 4)}
            if burn > self.slo.burn_threshold:
                # degraded, NOT unhealthy: /healthz stays 200 (load
                # balancers keep routing) while the reason is machine-
                # readable — the hook the SLO-aware scheduler steers on
                doc["status"] = "degraded"
                doc["degraded_reason"] = (
                    f"ttft_slo_burn_rate {burn:.2f} > "
                    f"{self.slo.burn_threshold} (p99 "
                    f"{ttft[0.99]:.4f}s vs slo {self.slo.ttft_s}s over "
                    f"{self._win_ttft.count()} requests)")
        return doc

    def requests_doc(self, k: int = 10) -> dict:
        """The `/requests` section: aggregate summary + top-k slowest
        with attributed latency components."""
        doc = self.request_log.summary()
        doc["slowest_by_ttft"] = self.request_log.slowest(k, by="ttft_s")
        return doc

    def metrics_text(self) -> str:
        self._update_window_gauges()   # expire-on-read: see the docstring
        return self.metrics.render_prometheus()

    def serve(self, host: str = "127.0.0.1", port: int = 0):
        """/metrics + /healthz + /requests over this engine's registry;
        caller owns ``close()``."""
        from paddle_tpu.observe.health import HealthServer
        return HealthServer(registry=self.metrics, health_fn=self.health,
                            host=host, port=port,
                            requests_fn=self.requests_doc,
                            metrics_fn=self.metrics_text)

    def compile_counts(self) -> Dict[str, int]:
        """Compilations the tracker charged to this engine's two
        programs — the "one per bucket + one for decode" invariant."""
        return {"prefill": self._tracker.count("serving_engine.prefill"),
                "decode": self._tracker.count("serving_engine.decode")}



class SpecDecodeEngine(PagedDecodeEngine):
    """Speculative decoding over the paged pool: a small DRAFT model
    proposes ``spec_k`` tokens per scheduler step, the TARGET model
    verifies the whole window in ONE batched pass, and an on-device
    accept/reject epilogue emits every accepted draft token plus one
    correction/bonus token — up to ``spec_k + 1`` tokens per step at
    one verify dispatch instead of ``spec_k + 1`` decode dispatches.

    **Shared pool.** The draft keeps its own device pool (its layer
    count / head geometry differ) but with the SAME (num_blocks,
    block_size) grid, indexed through the SAME page table and host
    :class:`~paddle_tpu.serving.blocks.BlockPool`: every writer (chunk
    prefill, verify, propose) writes both pools at the same physical
    rows, so a content-hash that certifies a target block certifies
    the draft rows beside it — prefix-cache hits, preemption and
    resume need no draft-side bookkeeping at all.

    **The step.** ``propose`` runs the k draft decode steps as one
    ``lax.scan``-fused program (greedy argmax between iterations — one
    dispatch, not k); ``verify`` runs the ``W = k+1`` window through
    ``transformer.verify_step_paged`` (every reduction keeps the
    decode step's axis lengths, so each window row is BITWISE the
    decode step it replaces) with the accept/reject sampling tail
    fused in. Greedy output is therefore bitwise-identical to the
    target-only engine — acceptance changes HOW FAST tokens emit,
    never WHICH tokens (pinned in tests/test_spec_decode.py).

    Rejected rows' KV stays in the pool above the rewound cursor where
    nothing reads it; the next window overwrites it. The multi-tenant
    scheduler (tiers, budgets, preempt-to-blocks) is inherited
    unchanged — on the eviction-fallback resume the forced history
    replays through verify windows, with ``draft_verify`` keeping the
    draft pool position-faithful where propose's own proposals would
    diverge from the forced tokens.
    """

    def __init__(self, prefill: Callable, decode: Callable, params,
                 cache, *, draft_params, draft_cache,
                 draft_prefill: Callable, propose: Callable,
                 verify: Callable, draft_verify: Callable, spec_k: int,
                 tracker: Optional[_ct.CompileTracker] = None,
                 **kw):
        # no verify program over recurrent rows or a latent pool
        for leaf, skeleton in (("rec_state", "gated_hybrid"),
                               ("latent", "latent_moe")):
            if leaf in (cache or ()) or leaf in (draft_cache or ()):
                import importlib
                importlib.import_module(
                    f"paddle_tpu.models.{skeleton}").refuse(
                    "speculative decoding (SpecDecodeEngine)")
        if kw.get("tiers") is not None:
            # a spilled payload carries only TARGET pool rows; adopting
            # one would leave the draft pool's rows beside it stale —
            # the same desync import_prefix refuses below
            raise ValueError("SpecDecodeEngine does not support tiered "
                             "spill (draft pool rows cannot ride the "
                             "single-pool payload)")
        if tracker is None and "chunk_tokens" in kw:
            # the spec engine legitimately compiles roughly TWICE the
            # paged chunk-grid set (target + draft prefill programs)
            # plus propose/verify/draft_verify — keep the default
            # tracker's storm threshold above that
            chunk = min(int(kw.get("chunk_tokens",
                                   _blocks.DEFAULT_CHUNK_TOKENS)),
                        int(kw["cache_len"]))
            spans = max(1, int(kw["cache_len"]) // max(chunk, 1))
            cb = kw.get("chunk_buckets")
            nb = len(tuple(cb)) if cb else len(
                default_chunk_buckets(chunk))
            tracker = _ct.CompileTracker(
                storm_threshold=2 * spans * nb + 8)
        super().__init__(prefill, decode, params, cache,
                         tracker=tracker, **kw)
        self.spec_k = int(spec_k)
        if self.spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {spec_k}")
        self.draft_params = draft_params
        self._m_weight_bytes.set(_tree_nbytes((self.params, draft_params)))
        self.draft_cache = draft_cache
        self._draft_prefill_fn = draft_prefill
        self._propose_fn = propose
        self._verify_fn = verify
        self._draft_verify_fn = draft_verify
        self._valid = np.ones(self.batch, np.int32)
        reg = self.metrics
        self._m_spec_rounds = reg.counter(
            "engine_spec_rounds_total",
            "propose+verify rounds executed")
        self._m_spec_proposed = reg.counter(
            "engine_spec_proposed_tokens_total",
            "draft tokens proposed for verification")
        self._m_spec_accepted = reg.counter(
            "engine_spec_accepted_tokens_total",
            "proposed draft tokens the target accepted (the emitted "
            "correction/bonus token is not counted — acceptance "
            "measures the draft's hit rate, not throughput)")

    # -- construction ------------------------------------------------------
    @classmethod
    def from_params(cls, params, cfg, draft_params, draft_cfg, *,
                    spec_k: int = 4, batch: int, cache_len: int,
                    block_size: int = _blocks.DEFAULT_BLOCK_SIZE,
                    num_blocks: Optional[int] = None,
                    chunk_tokens: int = _blocks.DEFAULT_CHUNK_TOKENS,
                    chunk_buckets: Optional[Sequence[int]] = None,
                    seed: Optional[int] = None,
                    pallas: Optional[str] = None,
                    kv_dtype: Optional[str] = None, **kw):
        """In-process spec engine: jit the target paged pair plus the
        draft program set against live params. The draft must share
        the target's vocab (its proposals are target tokens) and cover
        ``cache_len`` positions; everything else about it may differ —
        smaller is the point."""
        import jax
        from paddle_tpu.models import transformer
        from paddle_tpu.ops.pallas import policy as _pallas_policy
        from paddle_tpu.serving import sampling
        if draft_cfg.vocab != cfg.vocab:
            raise ValueError(
                f"draft vocab {draft_cfg.vocab} != target vocab "
                f"{cfg.vocab}: proposals must be target token ids")
        if cache_len > cfg.max_len or cache_len > draft_cfg.max_len:
            raise ValueError(
                f"cache_len {cache_len} exceeds max_len (target "
                f"{cfg.max_len}, draft {draft_cfg.max_len})")
        nb = int(num_blocks if num_blocks is not None
                 else batch * (cache_len // block_size))
        conform = functools.partial(transformer.compute_dtype_params,
                                    cfg=cfg)
        params = conform(params)
        draft_params = transformer.compute_dtype_params(draft_params,
                                                        draft_cfg)
        prefill_fn, decode_fn = sampling.paged_step_fns(
            cfg, block_size, pallas=pallas)
        spec = sampling.paged_spec_fns(cfg, draft_cfg, block_size,
                                       spec_k, pallas=pallas,
                                       paths=decode_fn.kernel_paths)
        pool = transformer.init_block_pool(cfg, nb, block_size,
                                           kv_dtype=kv_dtype)
        draft_pool = transformer.init_block_pool(draft_cfg, nb,
                                                 block_size)
        jdf = jax.jit(decode_fn, donate_argnums=(1,))
        jvf = jax.jit(spec["verify"])
        if "decode_flops" not in kw:
            # MFU accounting numerator = ONE VERIFY ROUND's model FLOPs
            # (the program this engine actually dispatches per step)
            pages = np.zeros((batch, cache_len // block_size), np.int32)
            W = int(spec_k) + 1
            cost = _costs.lowered_cost(
                jvf, params, pool, np.zeros((batch, W), np.int32),
                np.zeros(batch, np.int32), np.ones(batch, np.int32),
                np.zeros(batch, bool), pages,
                np.zeros(batch, np.float32), np.zeros(batch, np.int32),
                np.int32(0))
            kw["decode_flops"] = (cost or {}).get("flops")
        return cls(jax.jit(prefill_fn, donate_argnums=(1,)), jdf, params,
                   pool, draft_params=draft_params, draft_cache=draft_pool,
                   draft_prefill=jax.jit(spec["draft_prefill"]),
                   propose=jax.jit(spec["propose"]), verify=jvf,
                   draft_verify=jax.jit(spec["draft_verify"]),
                   spec_k=spec_k, batch=batch, cache_len=cache_len,
                   block_size=block_size, num_blocks=nb,
                   chunk_tokens=chunk_tokens,
                   chunk_buckets=chunk_buckets, seed=seed,
                   kv_dtype=kv_dtype,
                   pallas_mode=_pallas_policy.pallas_mode(pallas),
                   kernel_paths=decode_fn.kernel_paths, conform=conform,
                   **kw)

    # -- scheduler ---------------------------------------------------------
    def _draft_chunk_hook(self, slot: int, padded, c: int, npages: int):
        jnp = self._jnp
        self.draft_cache = self._tracker.track_call(
            "serving_engine.draft_prefill", self._draft_prefill_fn,
            self.draft_params, self.draft_cache, jnp.asarray(padded),
            np.int32(c), jnp.asarray(self._pages[slot, :npages]))

    def _pre_decode(self):
        # a verify round writes up to `valid` rows per slot — allocate
        # every page the window touches (the admission reservation
        # covers them: pos + valid - 1 <= Tp + max_new - 1)
        for slot in np.flatnonzero(self._active):
            end = int(self._pos[slot]) + int(self._valid[slot]) - 1
            while end // self.block_size >= self._nalloc[slot]:
                self._alloc_page(slot)

    def _precompile_decode(self):
        # the spec round's three programs (this engine never
        # dispatches the plain decode), none with an active row: every
        # write drops
        jnp = self._jnp
        B, W = self.batch, self.spec_k + 1
        none = jnp.zeros(B, bool)
        pos, valid = jnp.asarray(self._pos), jnp.ones(B, jnp.int32)
        pages_dev = self._decode_extra()[0]
        win_dev = jnp.zeros((B, W), jnp.int32)
        _, self.draft_cache = self._tracker.track_call(
            "serving_engine.propose", self._propose_fn,
            self.draft_params, self.draft_cache,
            jnp.asarray(self._last), pos, none, valid, pages_dev)
        self.draft_cache = self._tracker.track_call(
            "serving_engine.draft_verify", self._draft_verify_fn,
            self.draft_params, self.draft_cache, win_dev, pos, valid,
            none, pages_dev)
        X, _, self.cache = self._tracker.track_call(
            "serving_engine.verify", self._verify_fn,
            self.params, self.cache, win_dev, pos, valid, none,
            pages_dev, jnp.asarray(self._temp),
            jnp.asarray(self._topk), np.int32(0))
        np.asarray(X)

    def step(self) -> List[EngineRequest]:
        """One scheduler iteration: admission + chunk prefill as the
        paged engine, then ONE propose+verify round for everything in
        flight (instead of one decode step). The round's phases:
        ``decode_stage`` sizes the windows and allocates their pages,
        ``decode_dispatch`` runs propose (and reads its proposals),
        draft_verify and verify until the last returns, ``decode_sync``
        reads the accepted tokens back. Serial: propose reads back, so
        no round is left in flight and ``_resolve`` has nothing to
        do."""
        finished: List[EngineRequest] = []
        self._open_step()
        self._schedule(finished)
        if self._active.any():
            jnp = self._jnp
            B, W = self.batch, self.spec_k + 1
            with self.phase("decode_stage") as stage:
                valid = np.ones(B, np.int32)
                forced = np.zeros(B, bool)
                for slot in np.flatnonzero(self._active):
                    req = self._slot_req[slot]
                    if self._slot_forced[slot]:
                        forced[slot] = True
                        valid[slot] = min(
                            W, 1 + len(self._slot_forced[slot]))
                    else:
                        cap = (req.prompt.size + req.max_new
                               - int(self._pos[slot]) - 1)
                        valid[slot] = max(min(W, cap), 1)
                self._valid = valid
                self._pre_decode()
                pages_dev = self._decode_extra()[0]
                window = np.zeros((B, W), np.int32)
                window[:, 0] = self._last
                act_prop = self._active & ~forced
            with self.phase("decode_dispatch") as dispatch:
                if act_prop.any():
                    props, self.draft_cache = self._tracker.track_call(
                        "serving_engine.propose", self._propose_fn,
                        self.draft_params, self.draft_cache,
                        jnp.asarray(self._last), jnp.asarray(self._pos),
                        jnp.asarray(act_prop), jnp.asarray(valid),
                        pages_dev)
                    window[:, 1:] = np.asarray(props)
                for slot in np.flatnonzero(forced):
                    # replay window: the known history IS the proposal
                    # set
                    f = list(self._slot_forced[slot])[:W - 1]
                    window[slot, 1:1 + len(f)] = f
                win_dev = jnp.asarray(window)
                if forced.any():
                    # keep the draft pool position-faithful on replay
                    # rows (propose writes were masked off for these
                    # slots)
                    self.draft_cache = self._tracker.track_call(
                        "serving_engine.draft_verify",
                        self._draft_verify_fn, self.draft_params,
                        self.draft_cache, win_dev,
                        jnp.asarray(self._pos), jnp.asarray(valid),
                        jnp.asarray(forced & self._active), pages_dev)
                X, n, self.cache = self._tracker.track_call(
                    "serving_engine.verify", self._verify_fn,
                    self.params, self.cache, win_dev,
                    jnp.asarray(self._pos), jnp.asarray(valid),
                    jnp.asarray(self._active), pages_dev,
                    jnp.asarray(self._temp), jnp.asarray(self._topk),
                    self._seed())
            with self.phase("decode_sync") as sync:
                X, n = np.asarray(X), np.asarray(n)
            now = self._close_decode((stage, dispatch, sync))
            self._m_spec_rounds.inc()
            with self.phase("emit"):
                for slot in np.flatnonzero(self._active):
                    req = self._slot_req[slot]
                    if forced[slot]:
                        f = self._slot_forced[slot]
                        m = min(int(valid[slot]), len(f))
                        for _ in range(m):
                            tok = f.popleft()
                        self._pos[slot] += m
                        self._last[slot] = tok
                        continue
                    nprop = max(int(valid[slot]) - 1, 0)
                    m = int(n[slot])
                    self._m_spec_proposed.inc(nprop)
                    self._m_spec_accepted.inc(max(m - 1, 0))
                    fin, used = False, 0
                    for j in range(m):
                        used += 1
                        if self._emit(req, int(X[slot, j]), now):
                            fin = True
                            break
                    if fin:
                        finished.append(req)
                    else:
                        self._pos[slot] += used
                        self._last[slot] = int(X[slot, used - 1])
        self._close_step()
        return finished

    def import_prefix(self, payload: bytes) -> int:
        """Refused on the spec engine: the transfer wire ships TARGET
        pool blocks only, and adopting them would break the shared-pool
        invariant (every content hash certifies the draft rows beside
        it — imported blocks have no draft rows, so propose would read
        garbage KV). Route disaggregated decode at target-only
        replicas; a spec replica still serves as a prefill exporter."""
        raise ValueError("import_prefix: a SpecDecodeEngine cannot "
                         "adopt transferred blocks (no draft-pool rows "
                         "travel on the wire) — use a target-only "
                         "decode replica for P/D disaggregation")

    # -- observability -----------------------------------------------------
    def acceptance_rate(self) -> Optional[float]:
        """Lifetime draft acceptance: accepted / proposed (None before
        the first proposal). 1.0 means every draft token survived
        verification — e.g. a draft identical to the target under
        greedy sampling."""
        prop = self._m_spec_proposed.value()
        if not prop:
            return None
        return self._m_spec_accepted.value() / prop

    def compile_counts(self) -> Dict[str, int]:
        c = super().compile_counts()
        c.update({
            "draft_prefill": self._tracker.count(
                "serving_engine.draft_prefill"),
            "propose": self._tracker.count("serving_engine.propose"),
            "verify": self._tracker.count("serving_engine.verify"),
            "draft_verify": self._tracker.count(
                "serving_engine.draft_verify")})
        return c

    def health(self) -> dict:
        doc = super().health()
        acc = self.acceptance_rate()
        doc["spec"] = {
            "k": self.spec_k,
            "rounds": int(self._m_spec_rounds.value()),
            "proposed": int(self._m_spec_proposed.value()),
            "accepted": int(self._m_spec_accepted.value()),
            "acceptance_rate": round(acc, 4) if acc is not None
            else None}
        return doc
