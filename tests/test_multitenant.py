"""Multi-tenant scheduling in the paged engine: tiered admission,
per-tenant token budgets (queue, never reject), preempt-to-blocks with
both resume paths BITWISE-identical to an unpreempted run, and the
tenant/tier observability surface."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import transformer
from paddle_tpu.observe.compile_tracker import CompileTracker
from paddle_tpu.serving import PagedDecodeEngine

CFG = transformer.TransformerConfig(
    vocab=40, d_model=16, n_heads=2, n_kv_heads=1, n_layers=2, d_ff=32,
    max_len=64, dtype=jnp.float32, use_rope=True)
PARAMS = transformer.init_params(jax.random.PRNGKey(0), CFG)

BS = 8


def _paged(batch=2, cache_len=32, num_blocks=None, params=None,
           cfg=None, **kw):
    return PagedDecodeEngine.from_params(
        params if params is not None else PARAMS,
        cfg if cfg is not None else CFG,
        batch=batch, cache_len=cache_len, block_size=BS,
        chunk_tokens=8, num_blocks=num_blocks, seed=0,
        tracker=CompileTracker(), **kw)


def _solo_tokens(prompt, max_new):
    """Reference run: the same request alone on a fresh engine."""
    eng = _paged(num_blocks=8)
    req = eng.submit(prompt, max_new=max_new)
    eng.run_until_idle()
    return list(req.tokens)


class TestPreemptToBlocks:
    def test_latency_arrival_preempts_exactly_one_victim(self, rng):
        """A latency-tier request that cannot reserve under a full pool
        preempts exactly ONE batch-tier victim — not the whole arena."""
        eng = _paged(batch=3, num_blocks=6)
        pa = rng.randint(0, 40, 8).astype(np.int32)
        pb = rng.randint(0, 40, 8).astype(np.int32)
        va = eng.submit(pa, max_new=16, tier="batch")    # 3 blocks
        vb = eng.submit(pb, max_new=16, tier="batch")    # 3 blocks
        for _ in range(4):
            eng.step()
        assert va.status == "running" and vb.status == "running"
        lat = eng.submit(rng.randint(0, 40, 8).astype(np.int32),
                         max_new=8, tier="latency")      # needs 2
        eng.step()
        assert lat.status in ("prefilling", "running")
        preempted = [r for r in (va, vb) if r.status == "preempted"]
        assert len(preempted) == 1
        assert int(eng.metrics.get(
            "engine_preemptions_total").value()) == 1
        eng.run_until_idle()
        assert {r.finish_reason for r in (va, vb, lat)} == \
            {"max_tokens"}
        assert eng.pool.idle

    def test_preempt_resume_remap_bitwise(self, rng):
        """Fast-path resume (every snapshot block survives in the LRU):
        the victim's final output is bitwise the unpreempted run's, and
        the resume was a pure host re-mapping (mode=remap)."""
        prompt = rng.randint(0, 40, 8).astype(np.int32)
        ref = _solo_tokens(prompt, 16)
        eng = _paged(num_blocks=4)
        v = eng.submit(prompt, max_new=16, tier="batch")
        for _ in range(6):
            eng.step()
        assert v.status == "running" and len(v.tokens) >= 3
        lat = eng.submit(rng.randint(0, 40, 8).astype(np.int32),
                         max_new=8, tier="latency")
        eng.step()
        assert v.status == "preempted"
        eng.run_until_idle()
        assert lat.finish_reason == "max_tokens"
        assert list(v.tokens) == ref
        assert int(eng.metrics.get("engine_resumes_total").value(
            mode="remap")) == 1
        assert eng.pool.idle

    def test_preempt_resume_replay_bitwise_after_eviction(self, rng):
        """Eviction fallback: a big latency allocation evicts the
        victim's parked blocks, so resume is a cache-hit chunked
        prefill + forced decode replay — output STILL bitwise."""
        prompt = rng.randint(0, 40, 8).astype(np.int32)
        ref = _solo_tokens(prompt, 16)
        eng = _paged(num_blocks=4)
        v = eng.submit(prompt, max_new=16, tier="batch")
        for _ in range(6):
            eng.step()
        # adversary's worst case = the whole 4-block pool: its lazy
        # allocations evict every parked victim block
        lat = eng.submit(rng.randint(0, 40, 16).astype(np.int32),
                         max_new=16, tier="latency")
        eng.step()
        assert v.status == "preempted"
        eng.run_until_idle()
        assert lat.finish_reason == "max_tokens"
        assert list(v.tokens) == ref
        assert int(eng.metrics.get("engine_resumes_total").value(
            mode="replay")) == 1
        assert eng.pool.idle

    def test_preempted_mid_prefill_requeues_and_completes(self, rng):
        """A victim still prefilling re-queues (no decode cursor to
        snapshot); its published chunk blocks make re-admission a
        prefix-cache hit, and the output matches a solo run."""
        prompt = rng.randint(0, 40, 24).astype(np.int32)   # 3 chunks
        ref = _solo_tokens(prompt, 8)
        eng = _paged(batch=3, num_blocks=6)
        d = eng.submit(rng.randint(0, 40, 8).astype(np.int32),
                       max_new=6, tier="batch")
        eng.step()                     # d decodes: chunks now run one
        assert d.status == "running"   # per step, bounding the stall
        v = eng.submit(prompt, max_new=8, tier="batch")    # 4 blocks
        eng.step()                                         # chunk 1
        assert v.status == "prefilling"
        lat = eng.submit(rng.randint(0, 40, 8).astype(np.int32),
                         max_new=8, tier="latency")
        eng.step()
        assert v.preemptions == 1
        eng.run_until_idle()
        assert lat.finish_reason == "max_tokens"
        assert list(v.tokens) == ref
        assert eng.pool.idle

    def test_latency_tier_admits_ahead_of_earlier_batch(self, rng):
        """Priority: with one free slot, a later latency arrival beats
        an earlier-queued batch request."""
        eng = _paged(batch=1, num_blocks=8)
        p = rng.randint(0, 40, 8).astype(np.int32)
        running = eng.submit(p, max_new=4, tier="batch")
        eng.step()
        b = eng.submit(p, max_new=4, tier="batch")
        lat = eng.submit(p, max_new=4, tier="latency")
        eng.run_until_idle()
        assert lat.first_token_t < b.first_token_t
        assert running.finish_reason == "max_tokens"


class TestTenantBudgets:
    def test_budget_exhaustion_queues_not_rejects(self, rng):
        """Over-budget submissions stay QUEUED (zero rejections) and
        complete once the tenant's earlier work frees tokens."""
        eng = _paged(batch=4, num_blocks=16,
                     tenant_budgets={"acme": 20})
        p = rng.randint(0, 40, 8).astype(np.int32)
        r1 = eng.submit(p, max_new=8, tenant="acme")     # charge 16
        r2 = eng.submit(p, max_new=8, tenant="acme")     # over budget
        eng.step()
        assert r1.status in ("prefilling", "running")
        assert r2.status == "queued"
        rejected = eng.metrics.get("engine_requests_rejected_total")
        assert all(rejected.value(reason=r) == 0
                   for r in ("bad_tier", "exceeds_pool"))
        eng.run_until_idle()
        assert r1.finish_reason == "max_tokens"
        assert r2.finish_reason == "max_tokens"
        assert r2.prefill_t > r1.finish_t   # admitted only after r1

    def test_budget_blocked_tenant_skipped_not_head_of_line(self, rng):
        """A budget-exhausted tenant's request must not block OTHER
        tenants behind it in the queue."""
        eng = _paged(batch=4, num_blocks=16,
                     tenant_budgets={"acme": 20})
        p = rng.randint(0, 40, 8).astype(np.int32)
        r1 = eng.submit(p, max_new=8, tenant="acme")
        r2 = eng.submit(p, max_new=8, tenant="acme")     # blocked
        r3 = eng.submit(p, max_new=8, tenant="other")    # skips past
        eng.step()
        assert r2.status == "queued"
        assert r3.status in ("prefilling", "running")
        eng.run_until_idle()
        assert all(r.finish_reason == "max_tokens"
                   for r in (r1, r2, r3))

    def test_own_charge_exceeding_budget_rejected_not_queued(self, rng):
        """A request whose OWN prompt+max_new exceeds its tenant's cap
        could never admit — it must reject with a counted reason, not
        queue forever (the budget-skip would livelock the drain)."""
        eng = _paged(batch=2, num_blocks=8,
                     tenant_budgets={"acme": 10})
        p = rng.randint(0, 40, 8).astype(np.int32)
        with pytest.raises(ValueError, match="budget"):
            eng.submit(p, max_new=8, tenant="acme")      # charge 16
        assert int(eng.metrics.get(
            "engine_requests_rejected_total").value(
            reason="exceeds_budget")) == 1
        assert eng.idle                  # nothing parked

    def test_tenant_state_pruned_at_zero(self, rng):
        """Unbudgeted tenant names off the wire must not accumulate:
        the in-flight map prunes at zero and gauge samples exist only
        for CONFIGURED budgets (bounded cardinality)."""
        eng = _paged(batch=2, num_blocks=8,
                     tenant_budgets={"acme": 64})
        p = rng.randint(0, 40, 8).astype(np.int32)
        for i in range(5):
            eng.submit(p, max_new=4, tenant=f"drive-by-{i}")
        eng.submit(p, max_new=4, tenant="acme")
        eng.run_until_idle()
        assert eng._tenant_used == {}    # all pruned at zero
        txt = eng.metrics_text()
        assert 'tenant="acme"' in txt
        assert "drive-by" not in txt
        assert sorted(eng.health().get("tenants", {})) == ["acme"]

    def test_infeasible_latency_does_not_mass_evict(self, rng):
        """A latency request that could never fit even after evicting
        every batch victim must not preempt anything."""
        eng = _paged(batch=3, cache_len=32, num_blocks=6)
        p = rng.randint(0, 40, 8).astype(np.int32)
        b1 = eng.submit(p, max_new=8, tier="batch")
        b2 = eng.submit(p, max_new=8, tier="batch")
        big = rng.randint(0, 40, 16).astype(np.int32)
        lat1 = eng.submit(big, max_new=16, tier="latency")   # 4 blocks
        for _ in range(4):
            eng.step()
        assert lat1.status in ("prefilling", "running")
        # a second big latency request: its 4 blocks can never fit
        # beside lat1's 4 in a 6-block pool no matter how many batch
        # victims die — nothing may be preempted for it
        lat2 = eng.submit(big, max_new=16, tier="latency")
        eng.step()
        assert int(eng.metrics.get(
            "engine_preemptions_total").value()) == 0
        eng.run_until_idle()
        assert all(r.finish_reason == "max_tokens"
                   for r in (b1, b2, lat1, lat2))

    def test_double_preemption_of_replay_victim_no_reemission(self, rng):
        """A victim resumed via the replay fallback and preempted AGAIN
        mid-replay-PREFILL (forced history pending, slot mid-chunk)
        must keep its un-replayed history across the re-queue — no
        token may ever be emitted twice, and the final output stays
        bitwise the solo run's."""
        # this config/prompt pair generates a POSITION-DEPENDENT token
        # sequence (tiny random models usually collapse to a constant,
        # which would make a restart-from-scratch re-emission
        # invisible — the distinguishing power is the point)
        cfg = transformer.TransformerConfig(
            vocab=64, d_model=32, n_heads=2, n_kv_heads=1, n_layers=2,
            d_ff=64, max_len=64, dtype=jnp.float32, use_rope=True)
        params = transformer.init_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.RandomState(7)
        mkw = dict(batch=3, num_blocks=8, params=params, cfg=cfg)
        prompt = rng.randint(0, 64, 16).astype(np.int32)   # 2 chunks
        solo = _paged(**mkw)
        sr = solo.submit(prompt, max_new=8)
        solo.run_until_idle()
        ref = list(sr.tokens)
        assert len(set(ref[:3])) >= 2    # restart WOULD be visible
        eng = _paged(**mkw)
        d = eng.submit(rng.randint(0, 40, 4).astype(np.int32),
                       max_new=24, tier="batch")   # keeps decode live
        eng.step()
        v = eng.submit(prompt, max_new=8, tier="batch")
        while not (v.status == "running" and len(v.tokens) >= 2):
            eng.step()
        emitted = list(v.tokens)
        eng._preempt(v.slot)                       # preempt #1
        # surgically evict one snapshot block so resume MUST replay
        b = eng.pool.lookup(v.snapshot["hashes"][0])
        eng.pool.unpublish(b)
        # resume: with d decoding, the replay prefill advances one
        # chunk per step — catch it mid-prefill with forced pending
        while not (v.status == "prefilling"
                   and eng._slot_forced[v.slot]):
            eng.step()
        eng._preempt(v.slot)                       # preempt #2
        assert v.preemptions == 2
        eng.run_until_idle()
        assert list(v.tokens) == ref               # nothing re-emitted
        assert list(v.tokens)[:len(emitted)] == emitted
        assert d.finish_reason == "max_tokens"
        assert eng.pool.idle

    def test_set_tenant_budget_runtime(self, rng):
        eng = _paged(batch=2, num_blocks=8)
        eng.set_tenant_budget("acme", 16)
        p = rng.randint(0, 40, 8).astype(np.int32)
        r1 = eng.submit(p, max_new=8, tenant="acme")
        r2 = eng.submit(p, max_new=8, tenant="acme")
        eng.step()
        assert r1.status != "queued" and r2.status == "queued"
        eng.set_tenant_budget("acme", None)              # uncap
        eng.step()
        assert r2.status != "queued"
        eng.run_until_idle()


class TestTierObservability:
    def test_bad_tier_rejected_with_counted_reason(self, rng):
        eng = _paged()
        p = rng.randint(0, 40, 8).astype(np.int32)
        with pytest.raises(ValueError, match="tier"):
            eng.submit(p, max_new=4, tier="turbo")
        assert int(eng.metrics.get(
            "engine_requests_rejected_total").value(
            reason="bad_tier")) == 1

    def test_records_carry_tenant_tier_preemptions(self, rng):
        eng = _paged(num_blocks=4)
        prompt = rng.randint(0, 40, 8).astype(np.int32)
        v = eng.submit(prompt, max_new=16, tier="batch", tenant="bulk")
        for _ in range(6):
            eng.step()
        eng.submit(prompt, max_new=8, tier="latency",
                   tenant="interactive")
        eng.run_until_idle()
        recs = {r["rid"]: r for r in eng.request_log.records()}
        assert recs[v.rid]["tenant"] == "bulk"
        assert recs[v.rid]["tier"] == "batch"
        assert recs[v.rid]["preemptions"] == 1
        lat_rec = [r for r in recs.values()
                   if r["tenant"] == "interactive"]
        assert lat_rec and lat_rec[0]["tier"] == "latency"

    def test_per_tier_window_gauges_and_health(self, rng):
        eng = _paged(batch=2, num_blocks=8)
        p = rng.randint(0, 40, 8).astype(np.int32)
        eng.submit(p, max_new=4, tier="latency")
        eng.submit(p, max_new=4, tier="batch")
        eng.run_until_idle()
        txt = eng.metrics_text()
        assert 'tier="latency"' in txt and 'tier="batch"' in txt
        doc = eng.health()
        tiers = doc["window"]["tiers"]
        assert set(tiers) == {"latency", "batch"}
        assert all(t["requests"] == 1 for t in tiers.values())
        assert doc["preempted_queued"] == 0

    def test_preempted_resumed_trace_events(self, rng):
        from paddle_tpu import observe
        buf = observe.default_buffer()
        if not buf.enabled or buf.capacity < 4096:
            buf = observe.set_trace_capacity(8192)
        buf.clear()
        eng = _paged(num_blocks=4)
        prompt = rng.randint(0, 40, 8).astype(np.int32)
        v = eng.submit(prompt, max_new=16, tier="batch")
        for _ in range(6):
            eng.step()
        eng.submit(prompt[:8], max_new=8, tier="latency")
        eng.run_until_idle()
        evs = [e for e in observe.trace_export()["traceEvents"]
               if e.get("id") == v.trace_id]
        names = [e["name"] for e in evs]
        assert "preempted" in names and "resumed" in names
        # every slice the preempt/resume cycle opened must close: a
        # dangling b corrupts any duration-nested trace viewer
        for phase in ("request", "queued", "prefill", "decode"):
            b = sum(1 for e in evs
                    if e["name"] == phase and e["ph"] == "b")
            e_ = sum(1 for e in evs
                     if e["name"] == phase and e["ph"] == "e")
            assert b == e_, (phase, b, e_, [
                (e["name"], e["ph"]) for e in evs])

    def test_abort_closes_queued_preempted_and_running(self, rng):
        """``abort_requests`` (an in-process replica kill) reaches every
        place a live request can be — the queue, the preempted line, a
        slot — closes exactly the slices each has open, and leaves
        nothing to schedule."""
        from paddle_tpu import observe
        buf = observe.default_buffer()
        if not buf.enabled or buf.capacity < 4096:
            buf = observe.set_trace_capacity(8192)
        buf.clear()
        eng = _paged(num_blocks=4)
        prompt = rng.randint(0, 40, 8).astype(np.int32)
        victim = eng.submit(prompt, max_new=16, tier="batch")
        for _ in range(6):
            eng.step()
        lat = eng.submit(prompt, max_new=8, tier="latency")
        eng.step()
        waiting = eng.submit(prompt, max_new=16, tier="batch")
        assert victim.status == "preempted" and eng.preempted_count == 1
        assert lat.status in ("prefilling", "running")
        assert waiting.status == "queued"
        assert eng.abort_requests("replica_killed") == 3
        assert eng.idle and eng.preempted_count == 0
        assert eng.metrics.get("engine_queue_depth").value() == 0
        events = observe.trace_export()["traceEvents"]
        for r in (victim, lat, waiting):
            assert (r.status, r.finish_reason) == \
                ("aborted", "replica_killed")
            evs = [e for e in events if e.get("id") == r.trace_id]
            assert "aborted" in [e["name"] for e in evs]
            for phase in ("request", "queued", "prefill", "decode"):
                b = sum(1 for e in evs
                        if e["name"] == phase and e["ph"] == "b")
                e_ = sum(1 for e in evs
                         if e["name"] == phase and e["ph"] == "e")
                assert b == e_, (r.tier, phase, b, e_)



class TestPoolUnpublish:
    def test_unpublish_drops_cache_entry_and_frees_lru(self):
        from paddle_tpu.serving import BlockPool
        pool = BlockPool(4, 8)
        pool.reserve(1)
        b = pool.alloc()
        pool.publish(b"digest-x", b)
        pool.release(b)                       # parks in LRU
        assert pool.lookup(b"digest-x") == b
        assert pool.cached_free_count == 1
        pool.unpublish(b)
        assert pool.lookup(b"digest-x") is None
        assert pool.cached_free_count == 0
        assert pool.free_count == 4
        pool.unpublish(b)                     # idempotent
