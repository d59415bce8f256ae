#!/usr/bin/env python
"""The serving engine vs lockstep LM serving under Poisson load.

Replays request traces against two serving surfaces:

- ``engine_paged`` — ``serving.PagedDecodeEngine``: block-table KV
  pool, chunked prefill interleaved with decode, content-hash prefix
  cache (shared prompts prefill once, concurrent same-prefix requests
  adopt each other's blocks mid-flight), on-device sampling.
- ``lockstep``    — the ``LMServer.generate``-shaped baseline: FIFO
  batch formation, one shared prompt bucket, every row decodes to the
  LONGEST request's max_new, host-side argmax per token.

The throughput phase additionally A/Bs the paged engine's hot-path
levers (figures of merit: tokens/sec and decode MFU per variant):

- ``engine_paged_int8``  — same engine, ``quantize_lm_params`` int8
  weights consumed natively by the decode step (in-scan dequant,
  1-byte weight reads per token; prefill dequantizes wholesale).
- ``engine_paged_kv8``   — same engine over an int8-quantized KV POOL
  (``kv_dtype="int8"``: write-time per-(position, head) quantization,
  dequant fused into the gather) — the decode-side KV-stream lever:
  throughput must hold while the pool holds ~4x the tokens per byte.
- ``engine_paged_pallas`` — same engine, flash-decode + chunked-prefill
  Pallas kernels + fused sampling epilogue (``ops/pallas/``), timed
  only where the ``PADDLE_TPU_PALLAS`` policy resolves ``on`` (TPU
  under ``auto``); off-TPU the artifact records the mode and skips the
  timed run, and every invocation instead replays tiny greedy traces
  through the interpret-mode kernels — fp32 AND quantized-KV pools —
  asserting ids identical to the XLA paths.

Beyond the two trace phases, three KV-quantization scoreboards:

- **capacity** — slots-at-equal-HBM: at the fp32 pool's byte budget,
  how many requests can be RESIDENT at once (admission control is the
  pool-capacity semantic: reservation math binds, slots don't) for
  fp32 vs int8 vs int4 pools. Figures ``slots_at_equal_hbm_*`` and the
  ``slots_int8_ge_2x_fp32`` contract.
- **cold_prefill** — a shared-prefix-free Poisson trace on a fresh
  engine: ``ttft_p50_cold_ms`` isolates the chunked-prefill path with
  zero cache hits (the TTFT half the prefill kernel targets).
- **quality** — ``kv_int8_rel_l2`` / ``kv_int4_rel_l2``: global rel-L2
  of quantized-pool decode logits vs the fp32 pool on a cold chunk
  walk, asserted under ``transformer.kv_rel_l2_budget`` (the PR-5
  tolerance-contract recipe).

TWO phases, each its own trace over the same request mix:

- **throughput** — every request arrives at t=0 (offered load
  saturates the engine), no adversary: wall clock measures CAPACITY,
  which is where the prefix cache pays (tokens/sec, block occupancy,
  hit counts).
- **latency** — Poisson arrivals at ``--rate`` (chosen so the engines
  keep up): TTFT percentiles measure the SCHEDULING path.
  ``--long-prompt-adversarial`` drops ONE near-``cache_len`` prompt
  mid-burst — the engine interleaves its chunks with decode steps.

Trace shaping: ``--shared-prefix-frac F`` injects one common system
prompt (``--shared-prefix-len`` tokens) into fraction F of each trace
— the "millions of users share a system prompt" regime.

Each (variant, phase) replays ``--repeats`` times on a FRESH engine
(cold prefix cache; compiled programs shared via one jit + tracker)
and reports the best run — the least-machine-interference estimate on
a noisy host. Engine compile discipline (one compile per (chunk
bucket, context span) pair + one decode) is asserted
via the compile tracker. A JSON artifact lands in benchmarks/runs/
(``--out`` to override; skipped under ``--smoke`` unless --out given).

Usage: python benchmarks/serving_bench.py [--requests 96] [--batch 8]
           [--rate 16] [--shared-prefix-frac 0.5]
           [--long-prompt-adversarial] [--block-size 16]
           [--chunk-tokens 64] [--repeats 3]
           [--metrics-out=serving.jsonl] [--smoke]
"""

import argparse
import datetime
import gc
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

from bench_metrics import metrics_write as _metrics_write  # noqa: E402
from bench_metrics import resolve_metrics_out  # noqa: E402
from paddle_tpu.serving.blocks import (  # noqa: E402
    DEFAULT_BLOCK_SIZE, DEFAULT_CHUNK_TOKENS)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# --metrics-out=PATH (or BENCH_METRICS_OUT): JSONL trail next to the
# stdout JSON lines, bench.py conventions (inline append, never fatal)
METRICS_OUT = resolve_metrics_out()


def metrics_write(**rec):
    _metrics_write(METRICS_OUT, **rec)


def write_artifact(results, suffix, args):
    """Date-stamped artifact write shared by the serving and fleet
    phases: same-day reruns get an ordering-preserving _b/_c suffix
    instead of overwriting the artifact the regression sentinel
    compares against (the zero_bench convention); --smoke skips the
    write unless --out was given explicitly."""
    out = args.out
    if out is None:
        base = os.path.join(REPO, "benchmarks", "runs",
                            f"{datetime.date.today()}_{suffix}")
        out = base + ".json"
        i = 0
        while os.path.exists(out) and not args.smoke:
            i += 1
            out = f"{base}_{chr(ord('a') + i)}.json"
    if args.out or not args.smoke:
        d = os.path.dirname(out)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(out, "w") as f:
            json.dump(results, f, indent=2)
        print(f"wrote {out}", file=sys.stderr)


def _pct(vals, q):
    if not vals:
        return 0.0
    s = sorted(vals)
    return s[min(len(s) - 1, int(q * (len(s) - 1) + 0.5))]


def build_workload(n, rate, prompt_lens, max_news, vocab, seed, *,
                   shared_frac=0.0, shared_len=0, adversarial=False,
                   cache_len=0, adversarial_max_new=8, burst=0):
    """[(arrival_s, prompt ids, max_new)] — Poisson arrivals, mixed
    prompt/output lengths (the batch-formation-hostile shape).

    ``shared_frac`` of the requests get one common ``shared_len``-token
    system prompt prepended (prefix-cache traffic); ``adversarial``
    additionally inserts ONE near-``cache_len`` prompt arriving
    MID-BURST: the ``burst`` trace arrivals after the midpoint are
    compressed to land milliseconds behind it — the field study's
    long-multimodal-prompt-vs-interactive-traffic collision: the
    engine interleaves the victims' (often prefix-cache-hit) chunks
    with the adversary's."""
    rng = np.random.RandomState(seed)
    prefix = rng.randint(0, vocab, shared_len).astype(np.int32)
    t, work = 0.0, []
    for _ in range(n):
        t += rng.exponential(1.0 / rate)
        tp = int(prompt_lens[rng.randint(len(prompt_lens))])
        prompt = rng.randint(0, vocab, tp).astype(np.int32)
        if shared_frac > 0 and rng.rand() < shared_frac:
            prompt = np.concatenate([prefix, prompt])
        work.append((t, prompt,
                     int(max_news[rng.randint(len(max_news))])))
    if adversarial:
        tp_adv = cache_len - adversarial_max_new
        mid = len(work) // 2
        t_mid = work[mid][0]
        for j in range(mid, min(mid + burst, len(work))):
            work[j] = ((t_mid + (j - mid + 1) * 1e-3,) + work[j][1:])
        work.append((t_mid, rng.randint(0, vocab, tp_adv).astype(np.int32),
                     adversarial_max_new))
        work.sort(key=lambda w: w[0])
    return work


def _replay(eng, work):
    """Wall-clock trace replay against either engine; samples slot and
    block occupancy per scheduler step."""
    reqs, i, t0 = [], 0, time.perf_counter()
    occ_slots, occ_blocks = [], []
    while len(reqs) < len(work) or not eng.idle:
        now = time.perf_counter() - t0
        while i < len(work) and work[i][0] <= now:
            _, prompt, max_new = work[i]
            reqs.append(eng.submit(prompt, max_new))
            i += 1
        if eng.idle:
            time.sleep(min(max(work[i][0] - now, 0.0), 0.05))
            continue
        eng.step()
        occ_slots.append(eng.active_count)
        if hasattr(eng, "pool"):
            occ_blocks.append(eng.pool.in_use)
    wall = time.perf_counter() - t0
    return reqs, wall, occ_slots, occ_blocks


def _result(variant, eng, reqs, wall, occ_slots, occ_blocks):
    toks = sum(len(r.tokens) for r in reqs)
    lat = [r.latency_s for r in reqs]
    ttft = [r.ttft_s for r in reqs]
    mfu = eng.decode_mfu()
    r = {"variant": variant, "requests": len(reqs), "tokens": toks,
         "wall_s": round(wall, 4),
         "tokens_per_sec": round(toks / wall, 2),
         # decode MFU (PR-2 accounting): decode FLOPs / (mean step s ×
         # declared chip peak) — nominal-peak on CPU, honest on TPU
         "decode_mfu": round(mfu, 9) if mfu is not None else None,
         "pallas": eng.pallas_mode,
         "p50_latency_s": round(_pct(lat, 0.5), 4),
         "p99_latency_s": round(_pct(lat, 0.99), 4),
         "ttft_p50_s": round(_pct(ttft, 0.5), 4),
         "ttft_p99_s": round(_pct(ttft, 0.99), 4),
         "slot_occupancy_mean": round(
             float(np.mean(occ_slots)) / eng.batch, 3) if occ_slots
         else 0.0,
         "compiles": eng.compile_counts()}
    if occ_blocks:
        r.update({
            "kv_dtype": eng.kv_dtype,
            "kv_bytes_per_token": eng.kv_bytes_per_token,
            "blocks_total": eng.pool.num_blocks,
            "blocks_in_use_peak": int(max(occ_blocks)),
            "blocks_in_use_mean": round(float(np.mean(occ_blocks)), 1),
            "prefix_hit_blocks": int(eng.metrics.get(
                "engine_prefix_cache_hit_blocks_total").value()),
            "prefix_miss_blocks": int(eng.metrics.get(
                "engine_prefix_cache_miss_blocks_total").value()),
            "prefix_hit_tokens_total": sum(
                r_.prefix_hit_tokens for r_ in reqs)})
    return r


def attribution_section(work, reqs, burst, request_log):
    """Per-request tail-latency attribution of one latency-phase
    replay: top-10 slowest by TTFT with their component split, plus —
    when the trace carries the long-prompt adversary — the VICTIM
    summary: the burst requests arriving just behind the adversary,
    whose TTFT the chunked-prefill design promises is dominated by
    prefill-stall (bounded, one chunk at a time) rather than queue
    wait or decode.

    Records come from the ENGINE's own ring (``eng.request_log``) —
    one source of truth for the field mapping — joined to the trace's
    arrival times by rid."""
    from paddle_tpu.observe import requests as _oreq
    by_rid = {r["rid"]: r for r in request_log.records()}
    recs = []
    for i, r in enumerate(reqs):
        rec = by_rid.get(r.rid)
        assert rec is not None, (
            f"r{r.rid} missing from the engine request ring "
            f"(capacity {request_log.capacity}, "
            f"{request_log.evicted()} evicted) — trace too large "
            f"for the ring; raise PADDLE_TPU_REQUEST_LOG")
        rec = dict(rec)
        rec["arrival_s"] = round(work[i][0], 6)
        rec["attribution"] = _oreq.attribute(rec)
        recs.append(rec)
    slowest = sorted(recs, key=lambda r: r["ttft_s"] or 0.0,
                     reverse=True)[:10]
    out = {"requests": len(recs), "slowest_by_ttft": slowest}
    adversary = max(range(len(work)), key=lambda i: len(work[i][1]))
    t_adv = work[adversary][0]
    victims = [recs[i] for i in range(len(work))
               if i != adversary
               and t_adv <= work[i][0] <= t_adv + burst * 1e-3 + 1e-9]
    if victims:
        # dominance over the TTFT components (queue/own/stall): the
        # victims' damage is time-to-first-token — a long generation
        # afterwards (decode) is not the adversary's doing
        dom = {}
        for v in victims:
            d = v["attribution"]["ttft_dominant"]
            dom[d] = dom.get(d, 0) + 1
        out["victims"] = {
            "count": len(victims),
            "adversary_prompt_tokens": len(work[adversary][1]),
            "ttft_dominant_counts": dom,
            "ttft_dominant": max(dom, key=dom.get),
            "ttft_p50_s": round(_pct(
                [v["ttft_s"] for v in victims], 0.5), 6),
            "prefill_stall_p50_s": round(_pct(
                [v["prefill_stall_s"] for v in victims], 0.5), 6),
            "queue_wait_p50_s": round(_pct(
                [v["queue_wait_s"] for v in victims], 0.5), 6)}
    return out


def assert_lifecycles_joined(trace, reqs, buf):
    """Every completed request of the replay must have a fully-joined
    lifecycle in the exported trace: its async track present, every
    opened slice closed (b/e balanced), and a first_token marker — no
    orphan spans, no foreign tracks."""
    assert buf.dropped() == 0, (
        f"trace ring dropped {buf.dropped()} events — joins "
        f"unverifiable; raise PADDLE_TPU_TRACE_BUFFER")
    evs = [e for e in trace["traceEvents"] if e.get("cat") == "request"]
    by_id = {}
    for e in evs:
        by_id.setdefault(e["id"], []).append(e)
    for r in reqs:
        assert r.finish_reason is not None, f"r{r.rid} never finished"
        es = by_id.get(r.trace_id)
        assert es, f"request {r.trace_id}: no lifecycle events"
        b = sum(1 for e in es if e["ph"] == "b")
        e_ = sum(1 for e in es if e["ph"] == "e")
        assert b == e_ >= 1, (
            f"request {r.trace_id}: orphan async spans "
            f"({b} opened, {e_} closed)")
        assert any(e["name"] == "first_token" for e in es), (
            f"request {r.trace_id}: no first_token marker")
    extra = set(by_id) - {r.trace_id for r in reqs}
    assert not extra, f"orphan request tracks in trace: {sorted(extra)}"


def assert_fleet_lifecycles_joined(trace, reqs, buf):
    """Router-aware join check for a fleet replay: every completed
    request's track must be ONE connected tree — balanced b/e, exactly
    one router-side ``route`` root, the engine lifecycle (queued/
    prefill/decode/first_token) present on the SAME id — and a
    requeued request must still be single-rooted (its second placement
    re-joins the original trace, with the requeue marker and a second
    ``queued`` open on the track). No orphan tracks."""
    assert buf.dropped() == 0, (
        f"trace ring dropped {buf.dropped()} events — joins "
        f"unverifiable; raise PADDLE_TPU_TRACE_BUFFER")
    evs = [e for e in trace["traceEvents"]
           if e.get("cat") == "request" and e.get("ph") in "bne"]
    by_id = {}
    for e in evs:
        by_id.setdefault(e["id"], []).append(e)
    for r in reqs:
        assert r.status == "done", f"x{r.xid} ended {r.status!r}"
        es = by_id.get(r.trace_id)
        assert es, f"request {r.trace_id}: no lifecycle events"
        b = sum(1 for e in es if e["ph"] == "b")
        e_ = sum(1 for e in es if e["ph"] == "e")
        assert b == e_ >= 1, (
            f"request {r.trace_id}: orphan async spans "
            f"({b} opened, {e_} closed)")
        roots = [e for e in es if e["name"] == "route"
                 and e["ph"] == "b"]
        assert len(roots) == 1, (
            f"request {r.trace_id}: {len(roots)} route roots")
        names = [e["name"] for e in es]
        for engine_side in ("queued", "prefill", "decode",
                            "first_token"):
            assert engine_side in names, (
                f"request {r.trace_id}: missing {engine_side}")
        if r.requeues > 0:
            assert "requeue" in names and names.count("queued") >= 2, (
                f"requeued {r.trace_id} did not re-join: {names}")
    extra = set(by_id) - {r.trace_id for r in reqs}
    assert not extra, f"orphan request tracks in trace: {sorted(extra)}"


def _paged_programs(lens, chunk, bs, buckets):
    """The (chunk bucket, page-vector length) program set a COLD walk
    of the given prompt lengths reaches — one compile each (prefix
    hits and mid-flight adoption only ever SKIP chunk calls)."""
    from paddle_tpu.core import ragged
    progs = set()
    for n in lens:
        off = 0
        while off < n:
            c = min(n - off, chunk)
            b = ragged.bucket_length(c, buckets)
            progs.add((b, off // bs + -(-b // bs)))
            off += c
    return progs


def paged_factory(params, cfg, *, batch, cache_len, block_size,
                  chunk_tokens, num_blocks, tracker, pallas=None,
                  kv_dtype=None):
    """() -> fresh PagedDecodeEngine (cold pool + prefix cache) around
    ONE jitted program pair and ONE tracker, so repeat replays reuse
    the compile cache and the compile invariant spans all of them.
    ``pallas`` pins the PADDLE_TPU_PALLAS policy for the step programs;
    ``params`` may be the quantize_lm_params int8 tree (the int8
    serving variant); ``kv_dtype`` quantizes the KV pool itself
    ("int8"/"int4" — the engine_paged_kv8 variant)."""
    import jax

    from paddle_tpu.models import transformer
    from paddle_tpu.ops.pallas import policy as _pallas_policy
    from paddle_tpu.serving import PagedDecodeEngine, sampling
    from paddle_tpu.serving.engine import _decode_step_flops
    nb = int(num_blocks if num_blocks is not None
             else batch * (cache_len // block_size))
    prefill_fn, decode_fn = sampling.paged_step_fns(cfg, block_size,
                                                    pallas=pallas)
    jpf, jdf = jax.jit(prefill_fn), jax.jit(decode_fn)
    pool0 = transformer.init_block_pool(cfg, nb, block_size,
                                        kv_dtype=kv_dtype)
    flops = _decode_step_flops(
        jdf, params, pool0, batch,
        np.zeros((batch, cache_len // block_size), np.int32))
    mode = _pallas_policy.pallas_mode(pallas)

    def make():
        pool = transformer.init_block_pool(cfg, nb, block_size,
                                           kv_dtype=kv_dtype)
        return PagedDecodeEngine(
            jpf, jdf, params, pool, batch=batch, cache_len=cache_len,
            block_size=block_size, num_blocks=nb,
            chunk_tokens=chunk_tokens, seed=0, tracker=tracker,
            decode_flops=flops, pallas_mode=mode, kv_dtype=kv_dtype)

    return make


def warm_engine(factory, work, vocab):
    """One cold submit per distinct trace length covers every program
    the replay can reach; returns the compile counts to hold fixed."""
    wrng = np.random.RandomState(7)
    eng = factory()
    for n in sorted({len(p) for _, p, _ in work}):
        eng.submit(wrng.randint(0, vocab, n).astype(np.int32), 2)
        eng.run_until_idle()
    return dict(eng.compile_counts())


def engine_once(factory, variant, work, warm):
    """One replay on a FRESH engine (cold pool + prefix cache; the
    compiled programs and tracker are the factory's, shared)."""
    eng = factory()
    reqs, wall, occ_s, occ_b = _replay(eng, work)
    assert eng.compile_counts() == warm, (
        f"{variant}: timed replay recompiled: "
        f"{warm} -> {eng.compile_counts()}")
    return _result(variant, eng, reqs, wall, occ_s, occ_b)


def capacity_phase(params, cfg, *, cache_len, block_size, chunk_tokens,
                   batch, num_blocks, vocab, seed):
    """Slots-at-equal-HBM: at the fp32 pool's byte budget, how many
    requests can be RESIDENT at once per KV dtype. Admission is the
    measurement — the engine's worst-case reservation math is the
    pool-capacity semantic (decode never stalls mid-flight, so what
    admits is what serves) — taken as ``batch - free_slots`` after one
    scheduler step with a saturating submit wave and slot count sized
    past the pool's theoretical ceiling, so blocks, not slots, bind."""
    import jax.numpy as jnp

    from paddle_tpu.models import transformer
    from paddle_tpu.observe.compile_tracker import CompileTracker
    from paddle_tpu.serving import PagedDecodeEngine
    nb_fp = int(num_blocks if num_blocks is not None
                else batch * (cache_len // block_size))
    budget = nb_fp * block_size * transformer.kv_pool_bytes_per_token(
        cfg)
    prompt_len = min(chunk_tokens, cache_len // 2)
    max_new = min(16, cache_len - prompt_len)
    per_req = -(-(prompt_len + max_new) // block_size)
    # the baseline pool stores the MODEL dtype: "fp32" on the CPU bench
    # config, bf16 on TPU — name the keys honestly, because the >= 2x
    # contract is only reachable against a 4-byte baseline (vs bf16 the
    # int8+scale byte ratio is 4Dh/(2Dh+8) < 2 for every head_dim)
    base_key = ("fp32" if jnp.dtype(cfg.dtype).itemsize >= 4
                else jnp.dtype(cfg.dtype).name)
    out = {"pool_bytes_budget": int(budget),
           "prompt_tokens": prompt_len, "max_new": max_new,
           "blocks_per_request": per_req, "baseline_kv": base_key}
    rng = np.random.RandomState(seed + 17)
    slots = {}
    for kvd in (None, "int8", "int4"):
        bytes_tok = transformer.kv_pool_bytes_per_token(cfg, kvd)
        nb = max(int(budget // (block_size * bytes_tok)), per_req)
        cap = nb // per_req + 2           # slots can never be binding
        eng = PagedDecodeEngine.from_params(
            params, cfg, batch=cap, cache_len=cache_len,
            block_size=block_size, chunk_tokens=chunk_tokens,
            num_blocks=nb, seed=0, kv_dtype=kvd, pallas="off",
            tracker=CompileTracker(), decode_flops=None)
        for _ in range(cap):
            eng.submit(rng.randint(0, vocab, prompt_len)
                       .astype(np.int32), max_new)
        eng.step()                        # one admission wave
        key = base_key if kvd is None else kvd
        slots[key] = eng.batch - eng.free_slots
        out[f"slots_at_equal_hbm_{key}"] = slots[key]
        out[f"blocks_at_equal_hbm_{key}"] = nb
        out[f"kv_bytes_per_token_{key}"] = bytes_tok
    base = slots[base_key]
    out["slots_int8_ratio"] = round(slots["int8"] / max(base, 1), 3)
    out["slots_int4_ratio"] = round(slots["int4"] / max(base, 1), 3)
    # the contract: >= 2x against an fp32 baseline (the ISSUE figure);
    # against a narrower baseline the honest bound is the byte-ratio
    # arithmetic itself, minus admission-granularity slack
    byte_ratio = (out[f"kv_bytes_per_token_{base_key}"]
                  / out["kv_bytes_per_token_int8"])
    if base_key == "fp32":
        out["slots_int8_ge_2x_fp32"] = bool(slots["int8"] >= 2 * base)
        out["capacity_contract_ok"] = out["slots_int8_ge_2x_fp32"]
    else:
        out["capacity_contract_ok"] = bool(
            out["slots_int8_ratio"] >= 0.9 * byte_ratio)
    return out


def _chunk_walk(params, cfg, prompt, kv_dtype, *, block_size,
                chunk_tokens, pallas="off"):
    """Cold chunk-walk of one prompt on the engine's chunk grid (the
    same program shapes the engine compiles) into a fresh pool;
    returns (decode-step logits at position len(prompt), pool)."""
    import jax.numpy as jnp

    from paddle_tpu.core import ragged
    from paddle_tpu.models import transformer
    from paddle_tpu.serving import default_chunk_buckets
    bs = block_size
    n = len(prompt)
    pages_needed = -(-(n + 1) // bs)
    pool = transformer.init_block_pool(cfg, pages_needed + 1, bs,
                                       kv_dtype=kv_dtype)
    buckets = default_chunk_buckets(chunk_tokens)
    pages = np.arange(pages_needed + 1, dtype=np.int32)
    off, lg = 0, None
    while off < n:
        c = min(n - off, chunk_tokens)
        b = ragged.bucket_length(c, buckets)
        padded = np.zeros((1, b), np.int32)
        padded[0, :c] = prompt[off:off + c]
        pv = pages[:off // bs + -(-b // bs)]
        lg, pool = transformer.prefill_into_blocks(
            params, pool, jnp.asarray(padded), np.int32(c),
            jnp.asarray(pv), cfg, block_size=bs, pallas=pallas)
        off += c
    tok = jnp.argmax(lg, -1).astype(jnp.int32)
    logits, _ = transformer.decode_step_paged(
        params, pool, tok, jnp.asarray([n], jnp.int32),
        jnp.ones((1,), bool),
        jnp.asarray(pages[:pages_needed][None]), cfg, block_size=bs,
        pallas=pallas)
    return np.asarray(logits), pool


def kv_quality_probe(params, cfg, *, block_size, chunk_tokens, vocab,
                     seed):
    """Global rel-L2 of quantized-pool decode logits vs the fp32 pool
    on one cold multi-chunk prompt — recorded per dtype and ASSERTED
    under the documented grid-noise budget, so a committed artifact
    certifies generation quality on the host that produced it."""
    from paddle_tpu.models import transformer
    rng = np.random.RandomState(seed + 23)
    prompt = rng.randint(0, vocab, 2 * chunk_tokens + 5).astype(
        np.int32)
    ref, _ = _chunk_walk(params, cfg, prompt, None,
                         block_size=block_size,
                         chunk_tokens=chunk_tokens)
    out = {}
    for kvd in ("int8", "int4"):
        lg, _ = _chunk_walk(params, cfg, prompt, kvd,
                            block_size=block_size,
                            chunk_tokens=chunk_tokens)
        rel = float(np.linalg.norm(lg - ref) / np.linalg.norm(ref))
        budget = transformer.kv_rel_l2_budget(cfg, kvd)
        assert rel < budget, (
            f"kv_{kvd}_rel_l2 {rel:.4f} breaches the grid-noise "
            f"budget {budget:.4f} — wrong-scale-class bug")
        out[f"kv_{kvd}_rel_l2"] = round(rel, 6)
        out[f"kv_{kvd}_rel_l2_budget"] = round(budget, 6)
    return out


def tpu_compile_check(cfg, *, block_size, chunk_tokens, batch,
                      cache_len):
    """Deviceless COMPILE (not lowering) of the paged engine's programs
    for a TPU v5e, every kernel placed, per KV dtype —
    ``ops/pallas/aot.py``: libtpu runs Mosaic and XLA:TPU for a chip
    that is not attached. A kernel the compiler refuses raises with the
    compiler's message; what returns is what compiled, with each
    program's placement record, compile seconds and memory analysis.
    The compiled kernels need a block size that is a multiple of 128
    (``serving.blocks.DEFAULT_BLOCK_SIZE``)."""
    from paddle_tpu.models import transformer
    from paddle_tpu.ops.pallas import aot
    device = aot.topology_device()
    return {"device_kind": device.device_kind,
            "pool_layout": transformer.POOL_LAYOUT,
            **{kvd or "fp": aot.compile_engine_programs(
                cfg, device=device, batch=batch, cache_len=cache_len,
                block_size=block_size, chunk_tokens=chunk_tokens,
                kv_dtype=kvd)
               for kvd in (None, "int8", "int4")}}


def build_draft_pair(vocab, d_model, layers, heads, max_len, *,
                     alpha=0.05, draft_layers=1, seed=0):
    """A synthetically distilled (target, draft) pair: the target's
    layers beyond ``draft_layers`` get their residual-output weights
    (attn_out / mlp_out) scaled by ``alpha``, and the draft IS the
    target's first ``draft_layers`` layers + the shared embedding head.
    The target's compute cost is untouched (matmul shapes identical —
    small values are not faster), but its logits land close to the
    draft's, standing in for the trained/distilled draft a production
    deployment ships. What the spec phase measures is the ENGINE
    mechanics (propose/verify dispatch structure) at the acceptance
    rate this pair reaches — the acceptance itself is reported in the
    artifact, never assumed."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import transformer
    cfg = transformer.TransformerConfig(
        vocab=vocab, d_model=d_model, n_heads=heads, n_kv_heads=0,
        n_layers=layers, d_ff=d_model * 4, max_len=max_len,
        dtype=jnp.float32, use_rope=True)
    params = transformer.init_params(jax.random.PRNGKey(seed), cfg)
    blocks = dict(params["blocks"])
    for leaf in ("attn_out", "mlp_out"):
        w = np.array(blocks[leaf])
        w[draft_layers:] *= alpha
        blocks[leaf] = jnp.asarray(w)
    params = dict(params, blocks=blocks)
    draft_cfg = transformer.TransformerConfig(
        vocab=vocab, d_model=d_model, n_heads=heads, n_kv_heads=0,
        n_layers=draft_layers, d_ff=d_model * 4, max_len=max_len,
        dtype=jnp.float32, use_rope=True)
    draft_params = dict(params, blocks={
        k: v[:draft_layers] for k, v in params["blocks"].items()})
    return cfg, params, draft_cfg, draft_params


def spec_phase(args):
    """Speculative decoding A/B: the SAME greedy trace through a
    target-only paged engine and a SpecDecodeEngine sharing the pool.
    Figure of merit: ``spec_decode_speedup`` (tokens/sec ratio) — with
    output BITWISE-identical between the two engines asserted on every
    repeat (acceptance moves throughput, never tokens). The phase runs
    its own config (small draft-friendly model, decode-step-bound
    trace); the main phases' figures are untouched by it."""
    import jax

    from paddle_tpu.models import transformer
    from paddle_tpu.observe.compile_tracker import CompileTracker
    from paddle_tpu.serving import (PagedDecodeEngine, SpecDecodeEngine,
                                    sampling)
    if args.smoke:
        vocab, d_model, layers, heads = 64, 16, 2, 2
        cache_len, batch, k, n_req = 64, 2, 2, 4
        tp, max_new, bs, chunk, repeats = 8, 8, 8, 16, 1
    else:
        # decode-step-bound config (modest batch, long pool view):
        # where one verify dispatch replacing k+1 decode dispatches —
        # and one pool-view stream serving W rows — actually pays on
        # this backend; the draft pair's acceptance is ~0.95
        vocab, d_model, layers, heads = 256, 64, 2, 2
        cache_len, batch, k, n_req = 512, 6, 6, 24
        tp, max_new, bs, chunk, repeats = 16, 64, 16, 64, \
            max(1, args.repeats)
    cfg, params, draft_cfg, draft_params = build_draft_pair(
        vocab, d_model, layers, heads, cache_len + 32, seed=args.seed)
    rng = np.random.RandomState(args.seed + 31)
    prompts = [rng.randint(0, vocab, tp).astype(np.int32)
               for _ in range(n_req)]
    nb = batch * (cache_len // bs)
    kw = dict(batch=batch, cache_len=cache_len, block_size=bs,
              chunk_tokens=chunk, num_blocks=nb, seed=0,
              decode_flops=None)
    prefill_fn, decode_fn = sampling.paged_step_fns(cfg, bs,
                                                    pallas="off")
    jpf, jdf = jax.jit(prefill_fn), jax.jit(decode_fn)
    spec_fns = sampling.paged_spec_fns(cfg, draft_cfg, bs, k,
                                       pallas="off")
    jspec = {n: jax.jit(f) for n, f in spec_fns.items()}
    tr_t = CompileTracker(storm_threshold=99)
    tr_s = CompileTracker(storm_threshold=99)

    def mk_target():
        pool = transformer.init_block_pool(cfg, nb, bs)
        return PagedDecodeEngine(jpf, jdf, params, pool, tracker=tr_t,
                                 **kw)

    def mk_spec():
        pool = transformer.init_block_pool(cfg, nb, bs)
        dpool = transformer.init_block_pool(draft_cfg, nb, bs)
        return SpecDecodeEngine(
            jpf, jdf, params, pool, draft_params=draft_params,
            draft_cache=dpool, draft_prefill=jspec["draft_prefill"],
            propose=jspec["propose"], verify=jspec["verify"],
            draft_verify=jspec["draft_verify"], spec_k=k,
            tracker=tr_s, **kw)

    def once(mk):
        eng = mk()
        t0 = time.perf_counter()
        reqs = [eng.submit(p, max_new=max_new) for p in prompts]
        eng.run_until_idle()
        wall = time.perf_counter() - t0
        toks = sum(len(r.tokens) for r in reqs)
        return toks / wall, [list(r.tokens) for r in reqs], eng

    for mk in (mk_target, mk_spec):            # warm the programs
        eng = mk()
        eng.submit(prompts[0], max_new=4)
        eng.run_until_idle()
    best = {"target": 0.0, "spec": 0.0}
    acc = None
    for _ in range(repeats):                   # interleaved repeats
        tps_t, out_t, _ = once(mk_target)
        tps_s, out_s, eng_s = once(mk_spec)
        assert out_t == out_s, (
            "spec-decode greedy output diverged from the target-only "
            "engine — the bitwise verify contract is broken")
        best["target"] = max(best["target"], tps_t)
        best["spec"] = max(best["spec"], tps_s)
        acc = eng_s.acceptance_rate()
    # compile discipline: the spec engine adds its OWN program set
    # (draft prefill mirroring the chunk grid + one propose + one
    # verify) while the TARGET program set is unchanged — same chunk
    # programs, and the plain decode program never dispatches
    assert tr_s.count("serving_engine.prefill") == \
        tr_t.count("serving_engine.prefill"), (
        "spec engine changed the TARGET chunk-program set: "
        f"{tr_s.count('serving_engine.prefill')} vs "
        f"{tr_t.count('serving_engine.prefill')}")
    assert tr_s.count("serving_engine.draft_prefill") == \
        tr_t.count("serving_engine.prefill")
    assert tr_s.count("serving_engine.propose") == 1
    assert tr_s.count("serving_engine.verify") == 1
    assert tr_s.count("serving_engine.decode") == 0
    assert tr_t.count("serving_engine.decode") == 1
    speedup = best["spec"] / max(best["target"], 1e-9)
    out = {"spec_k": k, "vocab": vocab, "d_model": d_model,
           "layers": layers, "cache_len": cache_len, "batch": batch,
           "requests": n_req, "max_new": max_new,
           "draft_layers": draft_cfg.n_layers,
           "acceptance_rate": round(acc, 4) if acc is not None else None,
           "target_tokens_per_sec": round(best["target"], 1),
           "spec_tokens_per_sec": round(best["spec"], 1),
           "spec_decode_speedup": round(speedup, 3),
           "greedy_bitwise_ok": True}
    if not args.smoke:
        assert speedup >= 1.5, (
            f"spec_decode_speedup {speedup:.3f} below the 1.5 floor "
            f"(acceptance {acc}) — artifact would certify a broken "
            f"figure")
    return out


def build_tiered_workload(n, rate, vocab, seed, *, lat_frac=0.4,
                          lat_lens=(12, 16, 24), lat_new=(8, 12, 16),
                          bulk_lens=(48, 64, 96),
                          bulk_new=(32, 48, 64)):
    """[(arrival_s, prompt, max_new, tenant, tier)]: an interactive
    tenant (short prompts, short outputs, latency tier) sharing the
    engine with a bulk tenant (long prompts, long outputs, batch
    tier) — the tiered-traffic collision the Ascend field study names
    as the dominant serving regime."""
    rng = np.random.RandomState(seed)
    t, work = 0.0, []
    for _ in range(n):
        t += rng.exponential(1.0 / rate)
        if rng.rand() < lat_frac:
            tp = int(rng.choice(lat_lens))
            mn = int(rng.choice(lat_new))
            work.append((t, rng.randint(0, vocab, tp).astype(np.int32),
                         mn, "interactive", "latency"))
        else:
            tp = int(rng.choice(bulk_lens))
            mn = int(rng.choice(bulk_new))
            work.append((t, rng.randint(0, vocab, tp).astype(np.int32),
                         mn, "bulk", "batch"))
    return work


def _replay_tiered(eng, work, *, tiered):
    """Replay a tiered workload; ``tiered=False`` submits everything
    batch-tier (the single-class FIFO baseline) while keeping each
    request's INTENDED tier for the per-tier percentile split."""
    reqs, i, t0 = [], 0, time.perf_counter()
    while len(reqs) < len(work) or not eng.idle:
        now = time.perf_counter() - t0
        while i < len(work) and work[i][0] <= now:
            _, prompt, mn, tenant, tier = work[i]
            reqs.append((eng.submit(
                prompt, mn, tenant=tenant,
                tier=tier if tiered else "batch"), tier))
            i += 1
        if eng.idle:
            time.sleep(min(max(work[i][0] - now, 0.0), 0.05))
            continue
        eng.step()
    wall = time.perf_counter() - t0
    return reqs, wall


def multitenant_phase(args):
    """Multi-tenant scheduling A/B on ONE Poisson trace mixing an
    interactive (latency-tier) and a bulk (batch-tier) tenant over a
    deliberately TIGHT pool: ``tiered`` (real tiers — priority
    admission + preempt-to-blocks) vs ``fifo`` (everything batch-tier,
    the single-tenant PR-6 discipline). The scheduler must buy
    latency-tier TTFT separation (latency p99 < batch p99 under
    contention) without giving up aggregate goodput — under block
    pressure it actually GAINS goodput, because tiered admission skips
    past a reservation-blocked bulk head that FIFO would idle the pool
    behind."""
    import jax

    from paddle_tpu.models import transformer
    from paddle_tpu.observe.compile_tracker import CompileTracker
    from paddle_tpu.serving import PagedDecodeEngine, sampling
    if args.smoke:
        vocab, d_model, layers, heads = 64, 16, 2, 2
        cache_len, batch, n_req, rate = 64, 2, 8, 1e6
        bs, chunk, nb, repeats = 8, 16, 12, 1
        shape = dict(lat_lens=(4, 6), lat_new=(3, 4),
                     bulk_lens=(16, 24), bulk_new=(8, 16))
    else:
        vocab, d_model, layers, heads = 256, 64, 2, 2
        cache_len, batch, n_req, rate = 512, 8, 64, 150.0
        bs, chunk = 16, 64
        # tight pool + offered load far above capacity (the burst
        # regime): ~3 bulk requests' worst case fills the pool within
        # the first admission waves, so reservations (not slots) are
        # the contended resource and latency-tier arrivals landing
        # behind them actually preempt — on any machine speed
        nb, repeats = 30, max(1, args.repeats)
        shape = dict(bulk_new=(48, 64, 96))
    cfg = transformer.TransformerConfig(
        vocab=vocab, d_model=d_model, n_heads=heads, n_kv_heads=0,
        n_layers=layers, d_ff=d_model * 4, max_len=cache_len + 32,
        dtype=jax.numpy.float32, use_rope=True)
    params = transformer.init_params(jax.random.PRNGKey(args.seed), cfg)
    work = build_tiered_workload(n_req, rate, vocab, args.seed + 41,
                                 **shape)
    prefill_fn, decode_fn = sampling.paged_step_fns(cfg, bs,
                                                    pallas="off")
    jpf, jdf = jax.jit(prefill_fn), jax.jit(decode_fn)
    tracker = CompileTracker(storm_threshold=99)

    def mk():
        pool = transformer.init_block_pool(cfg, nb, bs)
        return PagedDecodeEngine(
            jpf, jdf, params, pool, batch=batch, cache_len=cache_len,
            block_size=bs, chunk_tokens=chunk, num_blocks=nb, seed=0,
            tracker=tracker, decode_flops=None)

    eng = mk()                                  # warm every program
    for n in sorted({len(p) for _, p, _, _, _ in work}):
        eng.submit(np.arange(n) % vocab, 2)
        eng.run_until_idle()

    def once(tiered):
        eng = mk()
        reqs, wall = _replay_tiered(eng, work, tiered=tiered)
        toks = sum(len(r.tokens) for r, _ in reqs)
        by_tier = {}
        for r, tier in reqs:
            by_tier.setdefault(tier, []).append(r.ttft_s)
        out = {"tokens_per_sec": round(toks / wall, 2),
               "wall_s": round(wall, 3),
               "preemptions": int(eng.metrics.get(
                   "engine_preemptions_total").value()),
               "resumes_remap": int(eng.metrics.get(
                   "engine_resumes_total").value(mode="remap")),
               "resumes_replay": int(eng.metrics.get(
                   "engine_resumes_total").value(mode="replay"))}
        for tier, tt in sorted(by_tier.items()):
            out[f"ttft_p50_{tier}_s"] = round(_pct(tt, 0.5), 4)
            out[f"ttft_p99_{tier}_s"] = round(_pct(tt, 0.99), 4)
            out[f"requests_{tier}"] = len(tt)
        assert eng.pool.idle, "block leak after multi-tenant trace"
        return out

    runs_t, runs_f = [], []
    for _ in range(repeats):
        runs_t.append(once(True))
        runs_f.append(once(False))
    # the reported run per variant is its best at ITS OWN figure of
    # merit (tiered = latency-tier p99, the SLO the scheduler serves;
    # fifo = goodput, the bar it sets) — but the GOODPUT comparison
    # must be best-vs-best at the SAME figure, or a machine-load spike
    # during tiered's best-latency run would masquerade as scheduler
    # overhead
    best_t = min(runs_t, key=lambda r: r["ttft_p99_latency_s"])
    best_f = max(runs_f, key=lambda r: r["tokens_per_sec"])
    sep_ok = (best_t["ttft_p99_latency_s"]
              < best_t["ttft_p99_batch_s"])
    goodput_ratio = (max(r["tokens_per_sec"] for r in runs_t)
                     / max(best_f["tokens_per_sec"], 1e-9))
    out = {"requests": n_req, "rate": rate, "batch": batch,
           "num_blocks": nb, "cache_len": cache_len,
           "tiered": best_t, "fifo": best_f,
           "tier_p99_separation_ok": bool(sep_ok),
           "tier_ttft_p99_ratio": round(
               best_t["ttft_p99_latency_s"]
               / max(best_t["ttft_p99_batch_s"], 1e-9), 4),
           # the scheduler's OWN effect: the latency tier's p99 under
           # tiered admission vs the SAME requests under FIFO — the
           # separation a short prompt gets for free cancels out of
           # this ratio
           "latency_p99_vs_fifo": round(
               best_t["ttft_p99_latency_s"]
               / max(best_f["ttft_p99_latency_s"], 1e-9), 4),
           "goodput_ratio_vs_fifo": round(goodput_ratio, 4),
           # >= within a 5% noise band: the two replays race the same
           # wall clock on a shared host; the tight-pool design makes
           # tiered genuinely >= 1.0 in the mean (admission skips the
           # blocked bulk head FIFO idles behind)
           "goodput_ge_fifo": bool(goodput_ratio >= 0.95)}
    if not args.smoke:
        assert sep_ok, (
            f"latency-tier p99 {best_t['ttft_p99_latency_s']} not "
            f"separated below batch-tier p99 "
            f"{best_t['ttft_p99_batch_s']}")
        assert sum(r["preemptions"] for r in runs_t) >= 1, (
            "multitenant trace never exercised preemption — the "
            "artifact would certify an idle scheduler")
    return out


def build_chat_workload(n_convos, turns, prefix_tokens, tail_tokens,
                        max_new, vocab, seed):
    """[(arrival_s, prompt, max_new)] — a multi-turn chat trace: each
    conversation carries its OWN ``prefix_tokens``-token system prompt
    and re-arrives once per turn with a fresh ``tail_tokens`` user
    message appended. Conversations are ROUND-ROBIN interleaved, so by
    the time a conversation's next turn lands, every other prefix has
    marched through the pool — with the working set sized past HBM
    (``--working-set-mult``) the prefix is always LRU-evicted before
    its reuse, the regime the tiered spill exists for."""
    rng = np.random.RandomState(seed)
    prefixes = [rng.randint(0, vocab, prefix_tokens).astype(np.int32)
                for _ in range(n_convos)]
    work = []
    for _ in range(turns):
        for c in range(n_convos):
            tail = rng.randint(0, vocab, tail_tokens).astype(np.int32)
            work.append((0.0, np.concatenate([prefixes[c], tail]),
                         max_new))
    return work


def tiered_cache_phase(args):
    """Tiered prefix cache (HBM -> host DRAM -> disk) vs
    evict-and-recompute on a multi-turn chat trace whose prefix
    working set is ``--working-set-mult``x the block pool.

    Both variants replay the SAME saturating trace on the SAME pool
    size; the baseline's only recourse on prefix reuse is a cold
    chunked prefill, the tiered engine re-admits demoted blocks
    through ``import_prefix`` (bitwise — the hit-vs-cold contract
    crosses tiers). The DRAM arena is sized to ~1/3 of the working
    set so the disk tier is genuinely exercised, not decorative.

    Figures: ``cold_prefill_tokens_avoided_frac`` (counter-derived,
    near-deterministic — the fraction of the baseline's cold-prefill
    block misses the tiers absorbed) and ``tiered_ttft_p99_ratio``
    (tiered/baseline TTFT p99 — < 1 wherever promotion is cheaper
    than the prefill FLOPs it replaces). Under ``--smoke`` the phase
    shrinks the trace and instead pins the BITWISE contract: every
    tiered-run output identical to a never-evicting big-pool engine's,
    with DRAM and disk promotions both proven live."""
    import shutil
    import tempfile

    import jax

    from paddle_tpu.models import transformer
    from paddle_tpu.observe.compile_tracker import CompileTracker
    from paddle_tpu.serving import PagedDecodeEngine, sampling
    mult = max(float(args.working_set_mult), 1.5)
    if args.smoke:
        vocab, d_model, layers, heads = 64, 16, 2, 2
        cache_len, batch = 64, 2
        bs, chunk, nb, repeats = 8, 16, 12, 1
        prefix_tokens, tail_tokens, turns, max_new = 16, 8, 2, 3
        mult = min(mult, 2.0)
    else:
        # d_model sized so a 256-token cold prefill costs MATERIAL
        # compute: the tiers trade a per-block host round-trip
        # (~size-independent python dispatch) against the prefill
        # FLOPs it replaces, and a toy width would measure the
        # dispatch, not the trade the feature exists for
        vocab, d_model, layers, heads = 256, 192, 2, 6
        cache_len, batch = 384, 4
        bs, chunk = 16, 32
        # two timed replays, not --repeats: the avoided-fraction
        # figure is counter arithmetic (deterministic), only the TTFT
        # ratio benefits from a best-of — and each replay pair costs
        # tens of seconds at this width
        nb, repeats = 64, max(1, min(2, args.repeats))
        prefix_tokens, tail_tokens, turns, max_new = 256, 32, 3, 8
    prefix_blocks = prefix_tokens // bs
    n_convos = max(2, -(-int(mult * nb) // prefix_blocks))
    work = build_chat_workload(n_convos, turns, prefix_tokens,
                               tail_tokens, max_new, vocab,
                               args.seed + 71)
    cfg = transformer.TransformerConfig(
        vocab=vocab, d_model=d_model, n_heads=heads, n_kv_heads=0,
        n_layers=layers, d_ff=d_model * 4, max_len=cache_len + 32,
        dtype=jax.numpy.float32, use_rope=True)
    params = transformer.init_params(jax.random.PRNGKey(args.seed), cfg)
    prefill_fn, decode_fn = sampling.paged_step_fns(cfg, bs,
                                                    pallas="off")
    jpf, jdf = jax.jit(prefill_fn), jax.jit(decode_fn)
    tracker = CompileTracker(storm_threshold=99)
    tmp = tempfile.mkdtemp(prefix="paddle_tpu_tiers_bench_")
    run_seq = [0]

    def mk(tiers=None, num_blocks=nb):
        pool = transformer.init_block_pool(cfg, num_blocks, bs)
        return PagedDecodeEngine(
            jpf, jdf, params, pool, batch=batch, cache_len=cache_len,
            block_size=bs, chunk_tokens=chunk, num_blocks=num_blocks,
            seed=0, tracker=tracker, decode_flops=None, tiers=tiers)

    # tier sizing off the REAL pool byte rate: DRAM holds ~1/3 of the
    # prefix working set (forcing the overflow onto disk), disk holds
    # the rest with room
    probe = mk()
    ws_bytes = int(n_convos * prefix_tokens * probe.kv_bytes_per_token)
    dram_bytes, disk_bytes = int(ws_bytes * 0.35), int(ws_bytes * 2)
    # warm every chunk program once (jpf/jdf are shared across engines,
    # so each timed replay below starts compiled)
    warm = mk()
    warm.submit(work[0][1], max_new)
    warm.run_until_idle()

    def once(tiered):
        tiers = None
        if tiered:
            run_seq[0] += 1
            d = os.path.join(tmp, f"run{run_seq[0]}")
            os.makedirs(d)
            tiers = {"dram_bytes": dram_bytes,
                     "disk_bytes": disk_bytes, "disk_dir": d}
        eng = mk(tiers)
        # GC off for the timed replay: the spill path allocates one
        # host buffer per demoted/promoted block, and in a process
        # carrying the earlier phases' object graph each of those
        # allocations can trigger a full-heap gc scan — a tax on the
        # tiered variant that scales with BENCH history, not with the
        # feature (standalone the ratio is ~0.8; late in the full
        # sweep it read >1 from gc pauses alone)
        gc.collect()
        gc.disable()
        try:
            reqs, wall, _, occ_blocks = _replay(eng, work)
        finally:
            gc.enable()
        ttft = [r.ttft_s for r in reqs]
        m = eng.metrics
        out = {"tokens_per_sec": round(
                   sum(len(r.tokens) for r in reqs) / wall, 2),
               "wall_s": round(wall, 3),
               "ttft_p50_s": round(_pct(ttft, 0.5), 4),
               "ttft_p99_s": round(_pct(ttft, 0.99), 4),
               "prefix_hit_blocks": int(m.get(
                   "engine_prefix_cache_hit_blocks_total").value()),
               "prefix_miss_blocks": int(m.get(
                   "engine_prefix_cache_miss_blocks_total").value()),
               "blocks_in_use_peak": int(max(occ_blocks))}
        if tiered:
            out["tier_hit_blocks"] = {
                t: int(m.get("engine_prefix_tier_hit_blocks_total")
                       .value(tier=t)) for t in ("hbm", "dram", "disk")}
            out["demotions"] = {
                t: int(m.get("engine_tier_demotions_total")
                       .value(tier=t)) for t in ("dram", "disk")}
            out["tier_corrupt"] = int(m.get(
                "engine_tier_corrupt_total").value())
        assert eng.pool.idle, "block leak after tiered-cache trace"
        return out, [r.output.tolist() for r in reqs]

    try:
        runs_t, runs_b = [], []
        for _ in range(repeats):
            runs_t.append(once(True))
            runs_b.append(once(False))
        best_t = min(runs_t, key=lambda r: r[0]["ttft_p99_s"])
        best_b = min(runs_b, key=lambda r: r[0]["ttft_p99_s"])
        if args.smoke:
            # bitwise across tiers: a never-evicting big-pool engine
            # serves every request warm — the tiered run (which
            # demoted, spilled to disk, and promoted back) must emit
            # IDENTICAL ids for all of them
            big = mk(num_blocks=len(work) * (
                -(-(prefix_tokens + tail_tokens + max_new) // bs)) + 8)
            ref_reqs, _, _, _ = _replay(big, work)
            ref_out = [r.output.tolist() for r in ref_reqs]
            assert best_t[1] == ref_out, (
                "tiered outputs diverged from the big-pool reference "
                "(hit-vs-cold contract broken across tiers)")
            assert best_b[1] == ref_out, (
                "baseline outputs diverged from the big-pool reference")
        th = best_t[0]["tier_hit_blocks"]
        assert th["dram"] + th["disk"] > 0, (
            "tiered trace never promoted a block — the figures would "
            "certify an idle spill path")
        assert best_t[0]["tier_corrupt"] == 0, best_t[0]
        miss_t = best_t[0]["prefix_miss_blocks"]
        miss_b = best_b[0]["prefix_miss_blocks"]
        avoided = 1.0 - miss_t / max(miss_b, 1)
        ratio = (best_t[0]["ttft_p99_s"]
                 / max(best_b[0]["ttft_p99_s"], 1e-9))
        out = {"requests": len(work), "conversations": n_convos,
               "turns": turns, "working_set_mult": round(mult, 2),
               "num_blocks": nb, "prefix_tokens": prefix_tokens,
               "dram_bytes": dram_bytes, "disk_bytes": disk_bytes,
               "tiered": best_t[0], "baseline": best_b[0],
               "cold_prefill_tokens_avoided_frac": round(avoided, 4),
               "tiered_ttft_p99_ratio": round(ratio, 4)}
        if not args.smoke:
            # the avoided fraction is counter arithmetic on a fixed
            # trace — assert the >= 0.5 claim outright (the TTFT ratio
            # breathes with the host and is gated by the sentinel's
            # absolute ceiling instead)
            assert avoided >= 0.5, (
                f"tiers absorbed only {avoided:.1%} of the baseline's "
                f"cold-prefill misses: {out}")
            assert th["disk"] > 0, (
                "disk tier never promoted on the full trace — DRAM "
                "sizing no longer forces the overflow down a tier")
        return out
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _replay_router(router, work):
    """Wall-clock trace replay against a fleet Router (mirrors
    ``_replay``'s arrival discipline; one router.step() per
    iteration pumps every in-process replica one engine step)."""
    reqs, i, t0 = [], 0, time.perf_counter()
    while len(reqs) < len(work) or not router.idle:
        now = time.perf_counter() - t0
        while i < len(work) and work[i][0] <= now:
            _, prompt, max_new = work[i]
            reqs.append(router.submit(prompt, max_new))
            i += 1
        if router.idle:
            time.sleep(min(max(work[i][0] - now, 0.0), 0.05))
            continue
        router.step()
    return reqs, time.perf_counter() - t0


def _fleet_victims(work, burst):
    """Indices of the burst arrivals compressed behind the adversarial
    long prompt (the longest prompt in the trace) — the victim set the
    TTFT figure scores."""
    lens = [len(p) for _, p, _ in work]
    adv = int(np.argmax(lens))
    return adv, set(range(adv + 1, min(adv + 1 + burst, len(work))))


def fleet_phase(args):
    """Serving-fleet A/B: a prefix-aware Router over R in-process
    replicas vs ONE engine at EQUAL total slots and pool blocks, on
    the shared-prefix trace with the long-prompt adversary mid-burst.

    Figures: router goodput ratio (fleet tokens/sec over the
    equal-chip single engine), victim TTFT p99 ratio (the burst
    arrivals stuck behind the adversary — the fleet quarantines the
    adversary's chunked prefill on ONE replica while the others keep
    serving, where the single engine makes every decoder share the
    stall), placement hit rate (shared-prefix traffic converging onto
    warm pools), an all-requests-completed bool, a P/D
    disaggregation bitwise check (prefill replica exports the KV
    prefix over the transfer wire, decode replica adopts it via the
    prefix-cache publish path, outputs equal the colocated run —
    asserted outright, it must never rot), an observability_overhead
    figure (fleet goodput with tracing+aggregation ON over OFF — the
    observability plane must stay off the hot path), and a chaos run
    (replica kill mid-burst) whose joined multi-replica trace, fleet
    /metrics render, and dead-replica firing→resolved alert pair are
    asserted outright (exported via --trace-out)."""
    from paddle_tpu.observe.compile_tracker import CompileTracker
    from paddle_tpu.serving import EngineReplica, default_chunk_buckets
    from paddle_tpu.serving.router import Router

    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import transformer

    R = 2 if args.smoke else 3
    per_batch = max(2, args.batch // 2)
    pages = args.cache_len // args.block_size
    cfg = transformer.TransformerConfig(
        vocab=args.vocab, d_model=args.d_model,
        n_heads=max(2, args.d_model // 32), n_kv_heads=0,
        n_layers=args.layers, d_ff=args.d_model * 4,
        max_len=args.cache_len,
        dtype=jnp.float32 if jax.default_backend() == "cpu"
        else jnp.bfloat16, use_rope=True)
    params = transformer.init_params(jax.random.PRNGKey(2), cfg)
    prompt_lens = [int(x) for x in args.prompt_lens.split(",")]
    max_news = [int(x) for x in args.max_new.split(",")]
    burst = R * per_batch
    work = build_workload(
        args.requests, args.rate, prompt_lens, max_news, args.vocab,
        args.seed + 5, shared_frac=max(args.shared_prefix_frac, 0.5),
        shared_len=args.shared_prefix_len, adversarial=True,
        cache_len=args.cache_len, burst=burst)
    adv_i, victims = _fleet_victims(work, burst)

    chunk = min(args.chunk_tokens, args.cache_len)
    storm = (args.cache_len // chunk) * len(
        default_chunk_buckets(chunk)) + 2
    mk_rep = paged_factory(
        params, cfg, batch=per_batch, cache_len=args.cache_len,
        block_size=args.block_size, chunk_tokens=args.chunk_tokens,
        num_blocks=per_batch * pages,
        tracker=CompileTracker(storm_threshold=storm), pallas="off")
    mk_single = paged_factory(
        params, cfg, batch=R * per_batch, cache_len=args.cache_len,
        block_size=args.block_size, chunk_tokens=args.chunk_tokens,
        num_blocks=R * per_batch * pages,
        tracker=CompileTracker(storm_threshold=storm), pallas="off")
    warm_rep = warm_engine(mk_rep, work, args.vocab)
    warm_single = warm_engine(mk_single, work, args.vocab)

    def once_single():
        eng = mk_single()
        reqs, wall, occ_s, occ_b = _replay(eng, work)
        assert eng.compile_counts() == warm_single, "single recompiled"
        toks = sum(len(r.tokens) for r in reqs)
        vt = sorted(r.ttft_s for i, r in enumerate(reqs)
                    if i in victims)
        return {"tokens_per_sec": round(toks / wall, 2),
                "wall_s": round(wall, 4), "tokens": toks,
                "victim_ttft_p99_s": round(_pct(vt, 0.99), 4),
                "requests": len(reqs),
                "completed": sum(1 for r in reqs
                                 if r.finish_reason is not None)}

    def once_fleet(observed=True):
        # observed=True is the PRODUCTION configuration (request
        # tracing + fleet metrics aggregation on, the router default);
        # observed=False is the dark baseline the observability_
        # overhead figure compares against
        reps = [EngineReplica(mk_rep(), f"r{i}") for i in range(R)]
        router = Router(reps, block_size=args.block_size,
                        chunk_tokens=args.chunk_tokens,
                        max_in_flight=per_batch * 2,
                        health_poll_s=0.5, trace=observed,
                        aggregate=observed)
        reqs, wall = _replay_router(router, work)
        for eng in (r.eng for r in reps):
            assert eng.compile_counts() == warm_rep, "fleet recompiled"
        toks = sum(len(r.tokens) for r in reqs)
        vt = sorted(r.ttft_s for i, r in enumerate(reqs)
                    if i in victims and r.ttft_s is not None)
        return {"tokens_per_sec": round(toks / wall, 2),
                "wall_s": round(wall, 4), "tokens": toks,
                "victim_ttft_p99_s": round(_pct(vt, 0.99), 4),
                "requests": len(reqs),
                "completed": sum(1 for r in reqs
                                 if r.status == "done"),
                "failed": sum(1 for r in reqs
                              if r.status == "failed"),
                "requeued": int(router._m_requeued.value()),
                "replicas": R, "slots_per_replica": per_batch,
                "placement_hit_rate": round(
                    router.placement_hit_rate(), 4)}

    repeats = max(1, args.repeats)
    single = fleet = fleet_dark = None
    for _ in range(repeats):       # interleaved, best goodput per side
        s, f = once_single(), once_fleet(observed=True)
        fd = once_fleet(observed=False)
        if single is None or s["tokens_per_sec"] > \
                single["tokens_per_sec"]:
            single = s
        if fleet is None or f["tokens_per_sec"] > \
                fleet["tokens_per_sec"]:
            fleet = f
        if fleet_dark is None or fd["tokens_per_sec"] > \
                fleet_dark["tokens_per_sec"]:
            fleet_dark = fd

    # P/D disaggregation bitwise check: colocated reference vs a
    # 1-prefill + 1-decode router fleet over the SAME compiled programs
    pd_prompts = [p for _, p, _ in work
                  if len(p) > args.chunk_tokens][:3]
    ref_eng = mk_rep()
    ref_out = []
    for p in pd_prompts:
        r = ref_eng.submit(p, 8)
        ref_eng.run_until_idle()
        ref_out.append(r.output)
    pf, dc = EngineReplica(mk_rep(), "pf"), EngineReplica(mk_rep(), "dc")
    pd_router = Router([pf, dc], block_size=args.block_size,
                       chunk_tokens=args.chunk_tokens, prefill=["pf"],
                       health_poll_s=0.5)
    pd_reqs = [pd_router.submit(p, 8) for p in pd_prompts]
    pd_router.run_until_idle()
    pd_ok = all(np.array_equal(r.output, w)
                for r, w in zip(pd_reqs, ref_out))
    assert pd_ok, "P/D disaggregated generation diverged from the " \
                  "colocated run"
    assert int(pd_router._m_pd_exports.value()) >= 1

    # chaos + trace-join: the observability acceptance run. One more
    # fleet with the span buffer captured end-to-end; kill the replica
    # holding the first placed request mid-run. Every request must
    # still complete, the requeued requests' spans must re-join their
    # ORIGINAL trace id (balanced b/e, exactly one router-side `route`
    # root), the fleet metrics render (what router /metrics serves)
    # must carry replica-labeled series and the pooled-TTFT quantile
    # gauges, and the dead-replica alert must fire and then resolve on
    # admin removal — asserted outright, the joined-timeline contract
    # must never rot.
    from paddle_tpu import observe
    buf = observe.default_buffer()
    if not buf.enabled or buf.capacity < 65536:
        buf = observe.set_trace_capacity(65536)
    buf.clear()
    ch_reps = [EngineReplica(mk_rep(), f"r{i}") for i in range(R)]
    ch_router = Router(ch_reps, block_size=args.block_size,
                       chunk_tokens=args.chunk_tokens,
                       max_in_flight=per_batch * 2, health_poll_s=0.0)
    ch_reqs = [ch_router.submit(p, m) for _, p, m in work]
    for _ in range(3):
        ch_router.step()
    placed = [r for r in ch_reqs if r.replica is not None]
    assert placed, "chaos run placed nothing before the kill"
    victim = placed[0].replica
    next(st.handle for st in ch_router._all
         if st.name == victim).kill()
    ch_router.run_until_idle()
    assert all(r.status == "done" for r in ch_reqs), \
        "chaos run lost requests"
    ch_requeued = [r for r in ch_reqs if r.requeues > 0]
    assert ch_requeued, "kill injection requeued nothing"
    mtext = ch_router.metrics_text()
    assert "fleet_ttft_window_seconds" in mtext, \
        "fleet /metrics missing pooled quantile gauges"
    assert 'fleet_engine_queue_depth{replica="' in mtext, \
        "fleet /metrics missing replica-labeled series"
    assert any(a["rule"] == "fleet_dead_replicas"
               for a in ch_router.alerts.firing()), \
        "replica death did not fire the dead-replica alert"
    ch_router.remove_replica(victim)
    ch_router.step()
    assert ch_router.alerts.firing() == [], \
        "dead-replica alert did not resolve after removal"
    alert_events = [(e["rule"], e["event"])
                    for e in ch_router.alerts.events]
    assert ("fleet_dead_replicas", "firing") in alert_events
    assert ("fleet_dead_replicas", "resolved") in alert_events
    trace = observe.trace_export(args.trace_out) if args.trace_out \
        else observe.trace_export()
    assert_fleet_lifecycles_joined(trace, ch_reqs, buf)
    if args.trace_out:
        print(f"wrote fleet trace to {args.trace_out} "
              f"({len(ch_reqs)} requests, {len(ch_requeued)} "
              f"requeued through the kill, all lifecycles joined)",
              file=sys.stderr)

    completed_ok = (fleet["failed"] == 0
                    and fleet["completed"] == len(work)
                    and fleet["requeued"] == 0)
    out = {
        "single": single, "fleet": fleet,
        "fleet_untraced": fleet_dark,
        "adversary_prompt_tokens": len(work[adv_i][1]),
        "victims": len(victims),
        "router_goodput_ratio": round(
            fleet["tokens_per_sec"]
            / max(single["tokens_per_sec"], 1e-9), 3),
        "victim_ttft_ratio": round(
            fleet["victim_ttft_p99_s"]
            / max(single["victim_ttft_p99_s"], 1e-9), 3),
        "placement_hit_rate": fleet["placement_hit_rate"],
        # goodput with tracing+aggregation ON over OFF on the same
        # machine — ~1.0 when the observability plane is off the hot
        # path; the sentinel holds it inside the noise band
        "observability_overhead": round(
            fleet["tokens_per_sec"]
            / max(fleet_dark["tokens_per_sec"], 1e-9), 3),
        "all_requests_completed": completed_ok,
        "pd_bitwise_ok": pd_ok,
        "pd_blocks_shipped": int(pd_router._m_pd_blocks.value()),
        "chaos_joined_ok": True,      # the asserts above are the proof
        "chaos": {"requests": len(ch_reqs),
                  "requeued": len(ch_requeued),
                  "killed_replica": victim,
                  "alert_pair_ok": True}}
    assert completed_ok, f"fleet lost requests: {fleet}"
    return out


def build_chaos_workload(n, rate, prompt_lens, max_news, vocab, seed,
                         *, shared_len, cache_len, peak_mult=4.0,
                         lat_frac=0.4):
    """Diurnal Poisson trace for the fleet-chaos phase: the arrival
    rate follows one sinusoidal day (trough -> peak at the middle ->
    trough, peak = ``peak_mult`` x base), ~``lat_frac`` of requests
    ride the latency tier, and EVERY request opens with one shared
    system prompt — so survivors hold the prefix warm and the
    rewarm-after-heal figure has something real to measure."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(1, vocab, size=shared_len).astype(np.int32)
    work, t = [], 0.0
    for i in range(n):
        frac = i / max(n - 1, 1)
        r = rate * (1.0 + (peak_mult - 1.0) * 0.5
                    * (1.0 - math.cos(2.0 * math.pi * frac)))
        t += float(rng.exponential(1.0 / r))
        plen = int(rng.choice(prompt_lens))
        mn = int(rng.choice(max_news))
        tail = rng.integers(1, vocab, size=plen).astype(np.int32)
        prompt = np.concatenate([shared, tail])[:cache_len - mn]
        tier = "latency" if rng.random() < lat_frac else "batch"
        work.append((t, prompt, mn, tier))
    return work


def _replay_fleet_chaos(router, work, *, ctrl=None, fleet=None,
                        kill_at=None):
    """Wall-clock chaos replay: submit through the door (admission
    sheds are counted, not errors), kill the busiest replica at
    ``kill_at`` seconds, step the controller (when given) every
    router step, and time the recovery."""
    from paddle_tpu.serving.router import AdmissionError
    reqs, shed, i = [], 0, 0
    killed = kill_t = healed_t = None
    max_q = 0
    t0 = time.perf_counter()
    while (i < len(work) or not router.idle
           or (ctrl is not None and killed is not None
               and healed_t is None
               and time.perf_counter() - t0 - kill_t < 30.0)):
        now = time.perf_counter() - t0
        while i < len(work) and work[i][0] <= now:
            _, prompt, mn, tier = work[i]
            try:
                reqs.append(router.submit(prompt, mn, tier=tier))
            except AdmissionError:
                shed += 1
            i += 1
        if kill_at is not None and killed is None and now >= kill_at:
            live = [st for st in router._all if st.state != "dead"]
            if any(st.in_flight > 0 for st in live):
                victim = max(live, key=lambda st: st.in_flight)
                if fleet is not None:
                    fleet.kill_name(victim.name)
                else:
                    victim.handle.kill()
                killed, kill_t = victim.name, now
        router.step()
        max_q = max(max_q, router.queue_depth)
        if ctrl is not None:
            ctrl.step()
            if (killed is not None and healed_t is None
                    and router.replica_states().get(killed) == "ok"):
                healed_t = time.perf_counter() - t0
        if router.idle:
            if i < len(work):
                time.sleep(min(max(work[i][0] - now, 0.0), 0.01))
            elif killed is not None and healed_t is None:
                time.sleep(0.002)   # drained: waiting out the heal
                #                     backoff alone
    return {"reqs": reqs, "shed": shed,
            "wall": time.perf_counter() - t0, "killed": killed,
            "kill_t": kill_t, "healed_t": healed_t, "max_queue": max_q}


def fleet_chaos_phase(args):
    """Fleet-control-plane A/B on a diurnal trace with an injected
    kill at the peak: a CONTROLLED fleet (FleetController healing +
    rewarm, door-side admission shedding batch past the queue bound)
    vs a STATIC baseline (same replicas, no controller, no admission
    — the dead replica stays dead and the door queues everything).

    Figures: latency-tier TTFT p99 under chaos (absolute ceiling —
    the band the control plane must hold), controlled-over-static
    TTFT ratio (the control plane must not be WORSE than doing
    nothing), healed capacity fraction (live replicas at the end over
    the provisioned fleet — the heal loop closed), recovery seconds
    (kill to the replacement reporting ok), rewarm blocks shipped to
    the replacement (cold-prefill work the KV relay avoided), and a
    shed-before-saturate boolean (the door shed batch work AND the
    queue never blew past the latency headroom — rejections happened
    at the door, not as timeouts in the queue)."""
    from paddle_tpu.observe import SloConfig
    from paddle_tpu.serving import EngineReplica
    from paddle_tpu.serving.autoscale import (FleetController,
                                              InProcessFleet)
    from paddle_tpu.serving.router import Router
    from paddle_tpu.observe.compile_tracker import CompileTracker

    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import transformer

    R = 2 if args.smoke else 3
    per_batch = max(2, args.batch // 2)
    pages = args.cache_len // args.block_size
    cfg = transformer.TransformerConfig(
        vocab=args.vocab, d_model=args.d_model,
        n_heads=max(2, args.d_model // 32), n_kv_heads=0,
        n_layers=args.layers, d_ff=args.d_model * 4,
        max_len=args.cache_len,
        dtype=jnp.float32 if jax.default_backend() == "cpu"
        else jnp.bfloat16, use_rope=True)
    params = transformer.init_params(jax.random.PRNGKey(3), cfg)
    prompt_lens = [int(x) for x in args.prompt_lens.split(",")]
    max_news = [int(x) for x in args.max_new.split(",")]
    rate = min(args.rate, 64.0)     # smoke's all-at-once 1e6 would
    #                                 erase the diurnal shape entirely
    work = build_chaos_workload(
        args.requests, rate, prompt_lens, max_news, args.vocab,
        args.seed + 7, shared_len=args.shared_prefix_len,
        cache_len=args.cache_len)
    kill_at = work[len(work) // 2][0]       # the peak of the day
    shed_max = max(2, args.requests // 8)

    mk_rep = paged_factory(
        params, cfg, batch=per_batch, cache_len=args.cache_len,
        block_size=args.block_size, chunk_tokens=args.chunk_tokens,
        num_blocks=per_batch * pages,
        tracker=CompileTracker(storm_threshold=10**9), pallas="off")
    warm_engine(mk_rep, [w[:3] for w in work], args.vocab)

    def lat_p99(reqs):
        # admitted latency-tier requests only: the tier the SLO prices
        vt = sorted(r.ttft_s for r in reqs
                    if r.tier == "latency" and r.ttft_s is not None)
        return round(_pct(vt, 0.99), 4)

    def side(reqs, res):
        return {"requests": len(reqs),
                "completed": sum(1 for r in reqs
                                 if r.status == "done"),
                "shed": res["shed"],
                "max_queue": res["max_queue"],
                "latency_ttft_p99_s": lat_p99(reqs),
                "wall_s": round(res["wall"], 4),
                "killed_replica": res["killed"]}

    # -- controlled: controller + admission --------------------------------
    fleet = InProcessFleet(lambda name: mk_rep())
    for i in range(R):
        fleet.spawn(f"r{i}")
    handles = [fleet.handle(f"r{i}") for i in range(R)]
    router = Router(handles, block_size=args.block_size,
                    chunk_tokens=args.chunk_tokens,
                    max_in_flight=per_batch * 2, health_poll_s=0.05,
                    shed_queue_max=shed_max,
                    slo=SloConfig(ttft_s=0.5, target=0.99,
                                  window_s=30.0))
    ctrl = FleetController(
        router, fleet, min_replicas=R, max_replicas=R,
        max_restarts=5, backoff_base=0.02, backoff_cap=0.1,
        rewarm=True, scale_up_queue=0, scale_down_idle_s=1e9)
    res_c = _replay_fleet_chaos(router, work, ctrl=ctrl, fleet=fleet,
                                kill_at=kill_at)
    reqs_c = res_c["reqs"]
    assert res_c["killed"] is not None, "chaos kill never fired"
    assert res_c["healed_t"] is not None, \
        "the controller never healed the killed replica"
    for _ in range(500):    # land the rewarm export/import ops the
        #                     replay left outstanding
        if router.outstanding == 0:
            break
        router.step()
        time.sleep(0.001)
    live_end = sum(1 for s in router.replica_states().values()
                   if s == "ok")
    rewarm_shipped = int(router._m_rewarm.value(result="shipped"))
    # no P/D tier in this phase: every imported block is a rewarm
    # relay — KV the replacement did NOT have to cold-prefill
    rewarm_blocks = int(router._m_pd_blocks.value())
    recovery_s = round(res_c["healed_t"] - res_c["kill_t"], 4)
    controlled = side(reqs_c, res_c)
    router.close()

    # -- static: same fleet shape, nobody at the wheel ----------------------
    s_handles = [EngineReplica(mk_rep(), f"r{i}") for i in range(R)]
    s_router = Router(s_handles, block_size=args.block_size,
                      chunk_tokens=args.chunk_tokens,
                      max_in_flight=per_batch * 2, health_poll_s=0.05)
    res_s = _replay_fleet_chaos(s_router, work, kill_at=kill_at)
    reqs_s = res_s["reqs"]
    static = side(reqs_s, res_s)
    s_router.close()

    admitted_ok = all(r.status == "done" for r in reqs_c)
    assert admitted_ok, "controlled run lost admitted requests"
    assert all(r.status == "done" for r in reqs_s), \
        "static run lost requests"
    c_p99, s_p99 = controlled["latency_ttft_p99_s"], \
        static["latency_ttft_p99_s"]
    shed_ok = (res_c["shed"] > 0
               and res_c["max_queue"] <= 2 * shed_max)
    return {
        "controlled": controlled, "static": static,
        "replicas": R, "shed_queue_max": shed_max,
        "kill_at_s": round(kill_at, 4),
        "chaos_latency_ttft_p99_s": c_p99,
        "chaos_ttft_ratio": round(c_p99 / max(s_p99, 1e-9), 3),
        "healed_capacity_frac": round(live_end / R, 3),
        "recovery_s": recovery_s,
        "rewarm_exports": rewarm_shipped,
        "rewarm_blocks_avoided": rewarm_blocks,
        "shed_before_saturate_ok": shed_ok,
        "all_admitted_completed": admitted_ok,
    }


def lockstep_factory(params, cfg, *, batch, cache_len, buckets):
    """(warm_fn, once_fn) for the pre-engine serving discipline: fill a
    FIFO batch (pad the tail group), share one prompt bucket, decode
    max(max_new) steps for everyone, sample on host from full logits."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.core import ragged
    from paddle_tpu.models import transformer

    prefill = jax.jit(
        lambda p, t: transformer.prefill(p, t, cfg, cache_len))
    step = jax.jit(
        lambda p, c, t, pos: transformer.decode_step(p, c, t, pos, cfg))

    def serve_group(group):
        """One lockstep batch decode, max(max_new) steps for all rows."""
        bucket = ragged.bucket_length(max(len(p) for _, p, _ in group),
                                      buckets)
        toks = np.zeros((batch, bucket), np.int32)
        for r, (_, p, _) in enumerate(group):
            # lockstep needs ONE shared prompt length: left-pad to the
            # group bucket (padding content doesn't affect step timing;
            # a real lockstep server refuses mixed lengths outright)
            toks[r, -len(p):] = p
        steps = max(m for _, _, m in group)
        logits, cache = prefill(params, jnp.asarray(toks))
        out = np.asarray(logits).argmax(-1).astype(np.int32)
        for j in range(steps - 1):
            # host-side sampling baseline: the full [B, vocab] logits
            # cross to numpy every token
            logits, cache = step(params, cache, jnp.asarray(out),
                                 jnp.asarray(bucket + j, jnp.int32))
            out = np.asarray(logits).argmax(-1).astype(np.int32)

    def warm(work):
        # compile each bucket the trace uses + the decode step
        for b in sorted({ragged.bucket_length(len(p), buckets)
                         for _, p, _ in work}):
            serve_group([(0.0, np.zeros(b, np.int32), 2)])

    def once(work):
        done, i, pending = 0, 0, []
        lat, ttfts, goodput = [], [], 0
        t0 = time.perf_counter()
        while i < len(work) or pending:
            now = time.perf_counter() - t0
            while i < len(work) and work[i][0] <= now:
                pending.append(work[i])
                i += 1
            if len(pending) >= batch or (i == len(work) and pending):
                group = pending[:batch]
                pending = pending[batch:]
                serve_group(group)
                end = time.perf_counter() - t0
                for arr, _p, m in group:
                    lat.append(end - arr)
                    ttfts.append(end - arr)   # lockstep: tokens land
                    goodput += m              # at the END of the batch
                done += len(group)
            elif i < len(work):
                time.sleep(min(max(work[i][0] - now, 0.0), 0.05))
        wall = time.perf_counter() - t0
        return {"variant": "lockstep", "requests": done,
                "tokens": goodput, "wall_s": round(wall, 4),
                "tokens_per_sec": round(goodput / wall, 2),
                "p50_latency_s": round(_pct(lat, 0.5), 4),
                "p99_latency_s": round(_pct(lat, 0.99), 4),
                "ttft_p50_s": round(_pct(ttfts, 0.5), 4),
                "ttft_p99_s": round(_pct(ttfts, 0.99), 4)}

    return warm, once


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=96,
                    help="trace length; sized so ONE adversarial "
                         "request cannot occupy the p99 index (TTFT "
                         "p99 measures the 99%, not the adversary)")
    ap.add_argument("--batch", type=int, default=8,
                    help="decode slots (= lockstep batch size)")
    ap.add_argument("--rate", type=float, default=16.0,
                    help="latency-phase Poisson arrival rate, req/s "
                         "(the throughput phase arrives all-at-once)")
    ap.add_argument("--vocab", type=int, default=2048)
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=512)
    ap.add_argument("--prompt-lens", default="16,32,64,96",
                    help="mixed prompt lengths (lockstep pads each "
                         "group to the max)")
    ap.add_argument("--max-new", default="4,8,16,32",
                    help="mixed output budgets (lockstep decodes every "
                         "row to the group max)")
    ap.add_argument("--shared-prefix-frac", type=float, default=0.5,
                    help="fraction of requests carrying one common "
                         "system prompt (prefix-cache traffic)")
    ap.add_argument("--shared-prefix-len", type=int, default=256,
                    help="length of the shared system prompt (the "
                         "field study's system-prompt regime)")
    ap.add_argument("--long-prompt-adversarial", action="store_true",
                    help="insert ONE near-cache_len prompt mid-burst "
                         "into the latency trace (the chunked-prefill "
                         "stress)")
    ap.add_argument("--block-size", type=int,
                    default=DEFAULT_BLOCK_SIZE,
                    help="paged-engine KV block size (tokens; the "
                         "engine default — the compiled kernels need "
                         "a multiple of 128)")
    ap.add_argument("--chunk-tokens", type=int,
                    default=DEFAULT_CHUNK_TOKENS,
                    help="paged-engine prefill chunk size (tokens)")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="paged pool size (default: "
                         "batch*cache_len/block_size)")
    ap.add_argument("--pallas", default=None,
                    choices=("auto", "on", "off", "interpret"),
                    help="PADDLE_TPU_PALLAS override for the "
                         "engine_paged_pallas variant (default: env > "
                         "auto — Pallas on TPU, skipped elsewhere; the "
                         "interpreter is a correctness path, far too "
                         "slow for a timed trace off --smoke)")
    ap.add_argument("--working-set-mult", type=float, default=10.0,
                    help="tiered_cache phase: prefix working set as a "
                         "multiple of the block pool (10x = the "
                         "capacity-starved regime the HBM->DRAM->disk "
                         "spill is for)")
    ap.add_argument("--repeats", type=int, default=3,
                    help="replays per (variant, phase); the best run "
                         "is reported (noise-robust on shared hosts)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics-out", default=None,
                    help="append JSONL records here (bench.py trail "
                         "conventions)")
    ap.add_argument("--out", default=None,
                    help="JSON artifact path (default: "
                         "benchmarks/runs/<date>_serving_paged.json; "
                         "skipped under --smoke unless given)")
    ap.add_argument("--trace-out", default=None,
                    help="export the per-request lifecycle trace of a "
                         "dedicated latency-phase replay (Chrome-trace "
                         "JSON) and assert every completed request's "
                         "lifecycle is fully joined — no orphan "
                         "spans. With --fleet: export the joined "
                         "multi-replica trace of the chaos run "
                         "(router route/queue/place spans + engine "
                         "lifecycles + the kill-and-requeue, one "
                         "connected tree per request)")
    ap.add_argument("--tpu-check", action="store_true",
                    help="deviceless COMPILE of the paged engine's "
                         "programs for a TPU v5e with every Pallas "
                         "kernel placed, per KV dtype (ops/pallas/"
                         "aot.py — the real compiler, no chip); needs "
                         "--block-size a multiple of 128; a refused "
                         "kernel raises with the compiler's message")
    ap.add_argument("--fleet", action="store_true",
                    help="run ONLY the serving-fleet phase (router "
                         "goodput + victim TTFT vs one engine at "
                         "equal total slots, placement hit rate, P/D "
                         "bitwise check) and write the date-stamped "
                         "serving_fleet artifact the router sentinel "
                         "family compares")
    ap.add_argument("--fleet-chaos", action="store_true",
                    help="run ONLY the fleet-control-plane chaos "
                         "phase (diurnal trace + kill at the peak: "
                         "controlled fleet with healing/rewarm/"
                         "admission vs a static baseline) and write "
                         "the date-stamped fleet_chaos artifact the "
                         "fleet sentinel family compares")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny preset for the tier-1 fast test: few "
                         "requests, near-zero inter-arrival gaps")
    args = ap.parse_args(argv)
    if args.smoke:
        args.requests, args.batch, args.rate = 6, 2, 1e6
        args.vocab, args.d_model, args.layers = 64, 16, 2
        args.cache_len = 64
        args.prompt_lens, args.max_new = "4,10", "4,8"
        args.shared_prefix_frac = max(args.shared_prefix_frac, 0.5)
        args.shared_prefix_len = 16
        args.block_size, args.chunk_tokens = 8, 16
        args.long_prompt_adversarial = True
        args.repeats = 1

    import jax
    import jax.numpy as jnp

    if args.fleet_chaos:
        # standalone control-plane chaos run: its own figures, its
        # own fleet_chaos artifact (the `fleet` sentinel family's
        # glob — distinct from serving_fleet, which the `router`
        # family matches)
        results = {"fleet_chaos": fleet_chaos_phase(args)}
        line = {"bench": "serving", "phase": "fleet_chaos",
                "platform": jax.default_backend(),
                **{k: v for k, v in results["fleet_chaos"].items()
                   if not isinstance(v, dict)}}
        print(json.dumps(line), flush=True)
        metrics_write(**line)
        for key in ("chaos_latency_ttft_p99_s", "chaos_ttft_ratio",
                    "healed_capacity_frac", "recovery_s",
                    "rewarm_exports", "rewarm_blocks_avoided",
                    "shed_before_saturate_ok",
                    "all_admitted_completed"):
            results[key] = results["fleet_chaos"][key]
        write_artifact(results, "fleet_chaos", args)
        return results

    if args.fleet:
        # standalone fleet run: its own figures, its own date-stamped
        # artifact (the check_regression `router` family's glob) —
        # the colocated serving figures above stay untouched
        results = {"fleet": fleet_phase(args)}
        line = {"bench": "serving", "phase": "fleet",
                "platform": jax.default_backend(),
                **{k: v for k, v in results["fleet"].items()
                   if not isinstance(v, dict)}}
        print(json.dumps(line), flush=True)
        metrics_write(**line)
        for key in ("router_goodput_ratio", "victim_ttft_ratio",
                    "placement_hit_rate", "observability_overhead",
                    "all_requests_completed", "pd_bitwise_ok",
                    "chaos_joined_ok"):
            results[key] = results["fleet"][key]
        results["fleet_tokens_per_sec"] = \
            results["fleet"]["fleet"]["tokens_per_sec"]
        write_artifact(results, "serving_fleet", args)
        return results

    from paddle_tpu.core import ragged
    from paddle_tpu.models import transformer
    from paddle_tpu.observe.compile_tracker import CompileTracker

    prompt_lens = [int(x) for x in args.prompt_lens.split(",")]
    max_news = [int(x) for x in args.max_new.split(",")]
    # the lockstep baseline LEFT-pads a group to its prompt bucket and
    # decodes every row from position bucket onward, so ITS cache (and
    # the model's position budget) must provision bucket + output on
    # top of the worst bucket = cache_len — the engines, which track
    # true prompt lengths, stay at cache_len (the HBM-parity point)
    lk_cache_len = args.cache_len + max(max_news)
    cfg = transformer.TransformerConfig(
        vocab=args.vocab, d_model=args.d_model,
        n_heads=max(2, args.d_model // 32), n_kv_heads=0,
        n_layers=args.layers, d_ff=args.d_model * 4,
        max_len=lk_cache_len,
        dtype=jnp.float32 if jax.default_backend() == "cpu"
        else jnp.bfloat16, use_rope=True)
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    shaping = dict(shared_frac=args.shared_prefix_frac,
                   shared_len=args.shared_prefix_len,
                   cache_len=args.cache_len)
    # throughput: offered load saturates the engine (capacity);
    # latency: Poisson at --rate (scheduling-path TTFT)
    work_tp = build_workload(args.requests, 1e9, prompt_lens, max_news,
                             args.vocab, args.seed, **shaping)
    work_lat = build_workload(
        args.requests, args.rate, prompt_lens, max_news, args.vocab,
        args.seed + 1, adversarial=args.long_prompt_adversarial,
        burst=args.batch, **shaping)
    all_lens = {len(p) for _, p, _ in work_tp + work_lat}
    # the lockstep prompt buckets must cover every trace length (the
    # engine needs no such bucket: chunked prefill)
    buckets = tuple(sorted({min(
        2 ** int(np.ceil(np.log2(max(n, 2)))), args.cache_len)
        for n in all_lens}))

    trace_cfg = {"trace_requests": args.requests, "rate": args.rate,
                 "shared_prefix_frac": args.shared_prefix_frac,
                 "shared_prefix_len": args.shared_prefix_len,
                 "long_prompt_adversarial": args.long_prompt_adversarial,
                 "block_size": args.block_size,
                 "chunk_tokens": args.chunk_tokens,
                 "cache_len": args.cache_len, "batch": args.batch,
                 "repeats": args.repeats}

    # the paged tracker's storm threshold sits above the chunk-grid
    # program ceiling: one compile per (bucket, span) is the DESIGN,
    # not a storm (the invariant below still pins the exact count)
    from paddle_tpu.io import lm_serving
    from paddle_tpu.ops.pallas import policy as pallas_policy
    from paddle_tpu.serving import default_chunk_buckets
    chunk = min(args.chunk_tokens, args.cache_len)
    n_chunk_buckets = len(default_chunk_buckets(chunk))
    storm = (args.cache_len // chunk) * n_chunk_buckets + 2
    paged_kw = dict(batch=args.batch, cache_len=args.cache_len,
                    block_size=args.block_size,
                    chunk_tokens=args.chunk_tokens,
                    num_blocks=args.num_blocks)
    paged_tr = CompileTracker(storm_threshold=storm)
    int8_tr = CompileTracker(storm_threshold=storm)
    # the baselines PIN pallas="off": on TPU the ambient policy would
    # otherwise resolve "on" and the "XLA engine" baseline would BE the
    # Pallas path — serving_pallas_speedup comparing Pallas vs Pallas
    mk_paged = paged_factory(params, cfg, tracker=paged_tr,
                             pallas="off", **paged_kw)
    # fp32-vs-int8: the same paged engine over quantize_lm_params
    # weights — decode reads int8 (in-scan dequant), prefill dequantizes
    # wholesale; XLA attention either way so the figure isolates the
    # weight dtype
    params_q8 = lm_serving.quantize_lm_params(params)
    mk_int8 = paged_factory(params_q8, cfg, tracker=int8_tr,
                            pallas="off", **paged_kw)
    # fp32-vs-int8-KV: the same engine over an int8-quantized POOL —
    # the decode-side KV stream at 1 byte/elt (+ scale rows); XLA
    # attention and fp32 weights either way so the figure isolates the
    # KV storage width
    kv8_tr = CompileTracker(storm_threshold=storm)
    mk_kv8 = paged_factory(params, cfg, tracker=kv8_tr, pallas="off",
                           kv_dtype="int8", **paged_kw)
    # XLA-vs-Pallas: one more paged variant with the flash-decode
    # kernel + fused sampling epilogue, run only where the policy turns
    # it on (auto = TPU; the interpreter is correctness-speed and gets
    # its own dedicated check under --smoke below)
    pallas_mode = pallas_policy.pallas_mode(args.pallas)
    # timed only where the compiled kernels are IN the program ("on";
    # building that engine raises where they cannot be)
    pallas_timed = pallas_mode == "on"
    pallas_tr = CompileTracker(storm_threshold=storm)
    mk_pallas = paged_factory(params, cfg, tracker=pallas_tr,
                              pallas=args.pallas, **paged_kw) \
        if pallas_timed else None

    lk_warm, lk_once = lockstep_factory(
        params, cfg, batch=args.batch, cache_len=lk_cache_len,
        buckets=buckets)

    results = {"pallas": {"mode": pallas_mode, "timed": pallas_timed}}
    repeats = max(1, args.repeats)
    for phase, work in (("throughput", work_tp), ("latency", work_lat)):
        engines = [("engine_paged", mk_paged)]
        if phase == "throughput":
            # the throughput phase carries the kernel/int8/kv8 A/Bs
            # (their figures of merit are tokens/sec and decode MFU)
            if mk_pallas is not None:
                engines.append(("engine_paged_pallas", mk_pallas))
            engines.append(("engine_paged_int8", mk_int8))
            engines.append(("engine_paged_kv8", mk_kv8))
        warms = {name: warm_engine(mk, work, args.vocab)
                 for name, mk in engines}
        lk_warm(work)
        # repeats INTERLEAVED across variants so ambient machine load
        # lands on all of them, not on whichever ran first; each phase
        # keeps the repeat best at ITS OWN figure of merit (capacity:
        # tokens/sec; scheduling: TTFT p99) for every variant alike
        def better(r, b):
            if phase == "latency":
                return r["ttft_p99_s"] < b["ttft_p99_s"]
            return r["tokens_per_sec"] > b["tokens_per_sec"]

        runners = [(name, (lambda mk=mk, name=name: engine_once(
            mk, name, work, warms[name]))) for name, mk in engines]
        runners.append(("lockstep", lambda: lk_once(work)))
        best = {}
        for _ in range(repeats):
            for variant, once in runners:
                r = once()
                if variant not in best or better(r, best[variant]):
                    best[variant] = r
        results[phase] = {}
        for variant, r in best.items():
            r.update({"bench": "serving", "phase": phase,
                      "platform": jax.default_backend(), **trace_cfg})
            results[phase][variant] = r
            print(json.dumps(r), flush=True)
            metrics_write(**r)

    # compile discipline across BOTH phases and all repeats: one
    # program per (chunk bucket, context span) + one
    # decode, regardless of paging, hits, adoption, weight dtype, or
    # attention engine
    progs = _paged_programs(all_lens, chunk, args.block_size,
                            default_chunk_buckets(chunk))
    # the int8/pallas A/B variants replay the throughput trace only —
    # their reachable program set is that phase's, not the union
    progs_tp = _paged_programs({len(p) for _, p, _ in work_tp}, chunk,
                               args.block_size,
                               default_chunk_buckets(chunk))
    for name, tr, want in (("paged", paged_tr, progs),
                           ("int8", int8_tr, progs_tp),
                           ("kv8", kv8_tr, progs_tp)) + (
            (("pallas", pallas_tr, progs_tp),) if pallas_timed else ()):
        assert tr.count("serving_engine.decode") == 1, name
        assert tr.count("serving_engine.prefill") == len(want), (
            f"{name} compile invariant: expected {len(want)} chunk "
            f"programs {sorted(want)}, saw "
            f"{tr.count('serving_engine.prefill')}")

    # the interpret-mode kernels must not rot on CPU-only CI: replay a
    # tiny greedy trace on pallas=interpret engines and demand ids
    # identical to the XLA engines' (greedy sampling is exact on both
    # paths). One prompt exceeds chunk_tokens so the CHUNKED-PREFILL
    # kernel runs with real context; the second pass repeats the whole
    # check over an int8-KV pool, so the FUSED-DEQUANT reads (decode +
    # prefill) are certified too. Runs under --smoke (tier-1) AND in
    # the full bench.
    srng = np.random.RandomState(11)
    n_long = min(chunk + 5, args.cache_len - 8)
    tiny = [srng.randint(0, args.vocab, n).astype(np.int32)
            for n in (5, 9, n_long)]
    for kvd in (None, "int8"):
        # XLA side reuses the throughput factories' compiled programs
        # (mk_paged / mk_kv8 are the same config at pallas="off"); the
        # compile-invariant asserts above already ran, so the tiny
        # replay's extra chunk shapes cannot contaminate them
        interp_tr = CompileTracker(storm_threshold=storm)
        variant_mks = [
            paged_factory(params, cfg, tracker=interp_tr,
                          pallas="interpret", kv_dtype=kvd, **paged_kw),
            mk_paged if kvd is None else mk_kv8]
        outs = []
        for mk in variant_mks:
            eng = mk()
            reqs = [eng.submit(p, max_new=4) for p in tiny]
            eng.run_until_idle()
            outs.append([r.output.tolist() for r in reqs])
        assert outs[0] == outs[1], (
            f"pallas interpret (kv_dtype={kvd}) diverged from the "
            f"XLA path:\n{outs[0]}\nvs\n{outs[1]}")
        key = ("interpret_check_ok" if kvd is None
               else f"interpret_check_kv{kvd[3:]}_ok")
        results["pallas"][key] = True
        line = {"bench": "serving", "phase": "pallas_interpret_check",
                "mode": "interpret", "kv_dtype": kvd or "none",
                "requests": len(tiny), "ok": True}
        print(json.dumps(line), flush=True)
        metrics_write(**line)

    # KV-quantization scoreboards: slots-at-equal-HBM (capacity),
    # cold-prefill TTFT (no cache hits — the chunked-prefill path
    # isolated), and the rel-L2 quality contracts
    results["capacity"] = capacity_phase(
        params, cfg, cache_len=args.cache_len,
        block_size=args.block_size, chunk_tokens=args.chunk_tokens,
        batch=args.batch, num_blocks=args.num_blocks, vocab=args.vocab,
        seed=args.seed)
    line = {"bench": "serving", "phase": "capacity",
            "platform": jax.default_backend(), **results["capacity"]}
    print(json.dumps(line), flush=True)
    metrics_write(**line)
    assert results["capacity"]["capacity_contract_ok"], (
        "int8-KV pool capacity fell short of its contract (2x vs an "
        "fp32 baseline; the byte-ratio bound vs a narrower one): "
        f"{results['capacity']}")

    work_cold = build_workload(
        args.requests, args.rate, prompt_lens, max_news, args.vocab,
        args.seed + 2, shared_frac=0.0, shared_len=0)
    cold_variants = [("xla", "off")] + (
        [("pallas", args.pallas)] if pallas_timed else [])
    results["cold_prefill"] = {"requests": args.requests,
                               "rate": args.rate}
    for cname, cmode in cold_variants:
        cold_tr = CompileTracker(storm_threshold=storm)
        mk_cold = paged_factory(params, cfg, tracker=cold_tr,
                                pallas=cmode, **paged_kw)
        warm_cold = warm_engine(mk_cold, work_cold, args.vocab)
        best_cold = None
        for _ in range(repeats):
            r = engine_once(mk_cold, f"engine_paged_cold_{cname}",
                            work_cold, warm_cold)
            if best_cold is None or r["ttft_p50_s"] < \
                    best_cold["ttft_p50_s"]:
                best_cold = r
        suffix = "" if cname == "xla" else "_pallas"
        results["cold_prefill"][f"ttft_p50_cold_ms{suffix}"] = round(
            best_cold["ttft_p50_s"] * 1000, 3)
        results["cold_prefill"][f"ttft_p99_cold_ms{suffix}"] = round(
            best_cold["ttft_p99_s"] * 1000, 3)
    line = {"bench": "serving", "phase": "cold_prefill",
            "platform": jax.default_backend(),
            **results["cold_prefill"]}
    print(json.dumps(line), flush=True)
    metrics_write(**line)

    results["quality"] = kv_quality_probe(
        params, cfg, block_size=args.block_size,
        chunk_tokens=args.chunk_tokens, vocab=args.vocab,
        seed=args.seed)
    line = {"bench": "serving", "phase": "kv_quality",
            **results["quality"]}
    print(json.dumps(line), flush=True)
    metrics_write(**line)

    # multi-tenant scheduling A/B (tiered vs FIFO on a tight pool) and
    # the speculative-decoding A/B — each on its own phase config, so
    # the figures above are untouched; both run under --smoke too
    # (compile asserts + bitwise contracts must not rot on tier-1)
    results["multitenant"] = multitenant_phase(args)
    line = {"bench": "serving", "phase": "multitenant",
            "platform": jax.default_backend(),
            **{k: v for k, v in results["multitenant"].items()
               if not isinstance(v, dict)}}
    print(json.dumps(line), flush=True)
    metrics_write(**line)
    results["tier_p99_separation_ok"] = \
        results["multitenant"]["tier_p99_separation_ok"]
    results["goodput_ge_fifo"] = \
        results["multitenant"]["goodput_ge_fifo"]

    # tiered prefix cache (HBM -> DRAM -> disk) vs evict-and-recompute
    # on the 10x-working-set chat trace; its two figures ride the
    # artifact top level for the sentinel's absolute floor/ceiling
    results["tiered_cache"] = tiered_cache_phase(args)
    line = {"bench": "serving", "phase": "tiered_cache",
            "platform": jax.default_backend(),
            **{k: v for k, v in results["tiered_cache"].items()
               if not isinstance(v, dict)}}
    print(json.dumps(line), flush=True)
    metrics_write(**line)
    results["cold_prefill_tokens_avoided_frac"] = \
        results["tiered_cache"]["cold_prefill_tokens_avoided_frac"]
    results["tiered_ttft_p99_ratio"] = \
        results["tiered_cache"]["tiered_ttft_p99_ratio"]

    results["spec_decode"] = spec_phase(args)
    line = {"bench": "serving", "phase": "spec_decode",
            "platform": jax.default_backend(),
            **results["spec_decode"]}
    print(json.dumps(line), flush=True)
    metrics_write(**line)
    results["spec_decode_speedup"] = \
        results["spec_decode"]["spec_decode_speedup"]

    if args.smoke:
        # fleet phase rides the tier-1 smoke so its bitwise contracts
        # (P/D disaggregation == colocated, zero lost requests) can't
        # rot; the goodput/victim-TTFT CLAIMS come from dedicated
        # --fleet runs and their own artifact
        results["fleet"] = fleet_phase(args)
        line = {"bench": "serving", "phase": "fleet",
                "platform": jax.default_backend(),
                **{k: v for k, v in results["fleet"].items()
                   if not isinstance(v, dict)}}
        print(json.dumps(line), flush=True)
        metrics_write(**line)

    if args.tpu_check:
        results["tpu_check"] = tpu_compile_check(
            cfg, block_size=args.block_size,
            chunk_tokens=args.chunk_tokens, batch=args.batch,
            cache_len=args.cache_len)
        line = {"bench": "serving", "phase": "tpu_check",
                "device_kind": results["tpu_check"]["device_kind"],
                "programs_compiled": {
                    k: sorted(v) for k, v in
                    results["tpu_check"].items() if isinstance(v, dict)}}
        print(json.dumps(line), flush=True)
        metrics_write(**line)

    # dedicated attribution replay: one more latency-phase run on a
    # fresh paged engine with request-lifecycle tracing captured — the
    # per-request tail-latency evidence (and, with --trace-out, the
    # joined-timeline export). Programs are already compiled, so this
    # replay adds no compiles (the invariant above already swept it).
    if args.trace_out or args.long_prompt_adversarial:
        from paddle_tpu import observe
        buf = observe.default_buffer()
        if not buf.enabled or buf.capacity < 4096:
            buf = observe.set_trace_capacity(65536)
        buf.clear()
        eng = mk_paged()
        reqs, _, _, _ = _replay(eng, work_lat)
        attribution = attribution_section(work_lat, reqs,
                                          burst=args.batch,
                                          request_log=eng.request_log)
        results["attribution"] = attribution
        line = {"bench": "serving", "phase": "attribution",
                "requests": attribution["requests"]}
        if "victims" in attribution:
            line.update({f"victims_{k}": v for k, v in
                         attribution["victims"].items()})
        print(json.dumps(line), flush=True)
        metrics_write(**line)
        if args.trace_out:
            trace = observe.trace_export(args.trace_out)
            assert_lifecycles_joined(trace, reqs, buf)
            print(f"wrote per-request trace to {args.trace_out} "
                  f"({len(reqs)} requests, all lifecycles joined)",
                  file=sys.stderr)

    tp = results["throughput"]
    int8_speedup = (tp["engine_paged_int8"]["tokens_per_sec"]
                    / max(tp["engine_paged"]["tokens_per_sec"], 1e-9))
    kv8_speedup = (tp["engine_paged_kv8"]["tokens_per_sec"]
                   / max(tp["engine_paged"]["tokens_per_sec"], 1e-9))
    figures = [
               # int8-vs-fp32 on the SAME engine: >1 where weight reads
               # bound decode (TPU); CPU pays the dequant ALU instead
               # and reports honestly below 1
               ("serving_int8_speedup", int8_speedup),
               # int8-KV-pool vs fp32-pool throughput on the SAME
               # engine: ~1 on CPU (the dequant ALU offsets the byte
               # win); TPU is where the KV-stream-bound step pays. The
               # capacity win (slots_at_equal_hbm) is dtype-arithmetic
               # and holds everywhere.
               ("serving_kv8_speedup", kv8_speedup)]
    if "engine_paged_pallas" in tp:
        figures.append((
            "serving_pallas_speedup",
            tp["engine_paged_pallas"]["tokens_per_sec"]
            / max(tp["engine_paged"]["tokens_per_sec"], 1e-9)))
    for metric, value in figures:
        line = {"bench": "serving", "metric": metric,
                "value": round(value, 3),
                "platform": jax.default_backend(), **trace_cfg}
        print(json.dumps(line), flush=True)
        metrics_write(**line)
        results[metric] = round(value, 3)

    write_artifact(results, "serving_paged", args)
    return results


if __name__ == "__main__":
    from paddle_tpu.utils import compile_cache
    compile_cache.configure()
    main()
