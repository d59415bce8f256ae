"""Continuous-batching decode engine: admission/recycling must not
perturb in-flight slots, whichever loop steps them (the paged engine's
decode step or the spec engine's propose+verify round), sampling runs
on device, and malformed submissions are refused."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import transformer
from paddle_tpu.observe.compile_tracker import CompileTracker
from paddle_tpu.ops import topk as ops_topk
from paddle_tpu.serving import (PagedDecodeEngine, SpecDecodeEngine,
                                sample_tokens)

CFG = transformer.TransformerConfig(
    vocab=40, d_model=16, n_heads=2, n_kv_heads=1, n_layers=2, d_ff=32,
    max_len=64, dtype=jnp.float32, use_rope=True)
PARAMS = transformer.init_params(jax.random.PRNGKey(0), CFG)


DRAFT_CFG = transformer.TransformerConfig(
    vocab=40, d_model=16, n_heads=2, n_kv_heads=1, n_layers=1, d_ff=32,
    max_len=64, dtype=jnp.float32, use_rope=True)
DRAFT_PARAMS = transformer.init_params(jax.random.PRNGKey(7), DRAFT_CFG)

# the two loops that step a slot: PagedDecodeEngine.step and
# SpecDecodeEngine.step (its own copy, a propose+verify round)
ENGINES = ("paged", "spec")


def _engine(kind="paged", batch=2, cache_len=32, seed=0):
    kw = dict(batch=batch, cache_len=cache_len, block_size=8,
              chunk_tokens=8, seed=seed, tracker=CompileTracker())
    if kind == "spec":
        return SpecDecodeEngine.from_params(
            PARAMS, CFG, DRAFT_PARAMS, DRAFT_CFG, spec_k=3, **kw)
    return PagedDecodeEngine.from_params(PARAMS, CFG, **kw)


def _sample_tokens_by_sort(logits, key, temperature, top_k):
    """``sample_tokens`` as it was up to PR 31: the k-th largest logit
    read out of a full descending sort of the row. Kept here as the
    reference the selection is held to, bitwise."""
    V = logits.shape[-1]
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    k = jnp.clip(top_k.astype(jnp.int32), 0, V)
    srt = jnp.sort(logits, axis=-1)[:, ::-1]
    kth = jnp.take_along_axis(srt, jnp.maximum(k - 1, 0)[:, None],
                              axis=-1)
    keep = (k[:, None] <= 0) | (logits >= kth)
    z = jnp.where(keep, logits, -jnp.inf)
    t = jnp.where(temperature > 0, temperature, 1.0)
    z = z / t[:, None].astype(jnp.float32)
    sampled = jax.random.categorical(key, z, axis=-1).astype(jnp.int32)
    return jnp.where(temperature > 0, sampled, greedy)


def _halves(rng, b, v):
    """Logits rounded to halves: ties across every threshold."""
    return (np.round(rng.randn(b, v) * 4) / 2).astype(np.float32)


def _neg_inf_columns(rng, b, v):
    x = _halves(rng, b, v)
    x[:, rng.rand(v) < 0.3] = -np.inf
    return x


def _signed_zeros(rng, b, v):
    x = _halves(rng, b, v)
    x[np.abs(x) < 1.0] = 0.0
    x[(x == 0) & (rng.rand(b, v) < 0.5)] = -0.0
    return x


def _all_equal(rng, b, v):
    return np.full((b, v), rng.randn(), np.float32)


_ROWS = {"halves": _halves, "neg_inf": _neg_inf_columns,
         "signed_zeros": _signed_zeros, "all_equal": _all_equal}


class TestOnDeviceSampling:
    def test_greedy_rows_argmax(self, rng):
        logits = jnp.asarray(rng.randn(4, 12), jnp.float32)
        out = sample_tokens(logits, jax.random.PRNGKey(0),
                            jnp.zeros(4), jnp.zeros(4, jnp.int32))
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(logits).argmax(-1))

    def test_top_k_restricts_support(self, rng):
        """With top_k=k, samples only ever land in the k largest."""
        logits = jnp.asarray(rng.randn(2, 20), jnp.float32)
        top3 = np.argsort(np.asarray(logits), -1)[:, -3:]
        for s in range(20):
            out = np.asarray(sample_tokens(
                logits, jax.random.PRNGKey(s),
                jnp.full(2, 1.5), jnp.full(2, 3, jnp.int32)))
            for row in range(2):
                assert out[row] in top3[row]

    def test_mixed_greedy_and_sampled_rows(self, rng):
        logits = jnp.asarray(rng.randn(2, 12), jnp.float32)
        out = np.asarray(sample_tokens(
            logits, jax.random.PRNGKey(3),
            jnp.asarray([0.0, 5.0]), jnp.zeros(2, jnp.int32)))
        assert out[0] == np.asarray(logits[0]).argmax()

    @pytest.mark.parametrize("batch", (1, 6))
    @pytest.mark.parametrize("rows", sorted(_ROWS))
    @pytest.mark.parametrize("vocab", (17, 128, 1000, 5003))
    def test_ids_bitwise_those_of_the_sort_spelling(self, vocab, rows,
                                                    batch):
        """The contract of selection: whatever the ties, infinities and
        signed zeros around the threshold, and whatever k and
        temperature each row asks for, the ids are the ones the full
        sort gave."""
        rng = np.random.RandomState(vocab * 7 + batch)
        new, old = jax.jit(sample_tokens), jax.jit(_sample_tokens_by_sort)
        ks = (-3, 0, 1, 2, 40, vocab - 1, vocab, vocab + 9)
        temps = (-1.0, 0.0, 0.8, 1.5)
        for i in range(len(ks)):
            logits = jnp.asarray(_ROWS[rows](rng, batch, vocab))
            key = jax.random.PRNGKey(1000 * vocab + i)
            top_k = jnp.asarray([ks[(i + r) % len(ks)]
                                 for r in range(batch)], jnp.int32)
            # row r of call i: every k meets a sampling temperature
            temp = jnp.asarray([temps[(2 + i // 4 + r) % len(temps)]
                                for r in range(batch)], jnp.float32)
            np.testing.assert_array_equal(
                np.asarray(new(logits, key, temp, top_k)),
                np.asarray(old(logits, key, temp, top_k)))

    def test_kth_largest_is_the_sorted_rows_kth(self, rng):
        """Every k of a small row, ties, signed zeros and infinities in
        it: the selected value is ``sort(x)[::-1][k - 1]`` (compared as
        a float, which is how the sampler reads it: ``-0.0 == +0.0``),
        and the key map is a bijection on the bits."""
        x = np.round(rng.randn(3, 37) * 2) / 2
        x[0, :5], x[1, 7:9], x[2, ::3] = -np.inf, np.inf, -0.0
        x = x.astype(np.float32)
        want = np.sort(x, axis=-1)[:, ::-1]
        f = jax.jit(ops_topk.kth_largest)
        for k in range(1, x.shape[1] + 1):
            got = np.asarray(f(jnp.asarray(x),
                               jnp.full((3, 1), k, jnp.int32)))
            np.testing.assert_array_equal(got[:, 0], want[:, k - 1])
        back = ops_topk.key_value(ops_topk.sortable_key(jnp.asarray(x)))
        np.testing.assert_array_equal(
            np.asarray(back).view(np.uint32), x.view(np.uint32))


@pytest.mark.parametrize("kind", ENGINES)
class TestEngineScheduling:
    def test_mid_flight_admission_does_not_perturb(self, kind, rng):
        """The continuous-batching invariant: a request admitted into a
        free slot changes NOTHING for its in-flight neighbour."""
        pa = rng.randint(0, 40, 5).astype(np.int32)
        pb = rng.randint(0, 40, 9).astype(np.int32)
        solo = _engine(kind)
        ra_solo = solo.submit(pa, max_new=8)
        solo.run_until_idle()

        eng = _engine(kind)
        ra = eng.submit(pa, max_new=8)
        for _ in range(2):
            eng.step()              # A mid-flight: first token, then a
        #                             decode step or a verify round
        assert 2 <= len(ra.tokens) < 8
        rb = eng.submit(pb, max_new=6)   # joins slot 1 mid-flight
        eng.run_until_idle()
        np.testing.assert_array_equal(ra.output, ra_solo.output)
        want_b = np.asarray(transformer.generate(
            PARAMS, jnp.asarray(pb[None]), CFG, max_new=6))[0]
        np.testing.assert_array_equal(rb.output, want_b)

    def test_eos_recycles_slot_for_queued_request(self, kind, rng):
        """EOS termination frees the slot and its blocks; the queued
        request fills it and decodes correctly in the recycled row."""
        pa = rng.randint(0, 40, 5).astype(np.int32)
        pc = rng.randint(0, 40, 7).astype(np.int32)
        probe = _engine(kind, batch=1)
        ra = probe.submit(pa, max_new=8)
        probe.run_until_idle()
        # pick an eos that first appears mid-stream (greedy stream is
        # deterministic, so the replay terminates exactly there)
        idx = next(i for i in range(1, len(ra.tokens))
                   if ra.tokens[i] not in ra.tokens[:i])
        eos = ra.tokens[idx]

        eng = _engine(kind, batch=1)   # one slot: C waits for A's EOS
        ra2 = eng.submit(pa, max_new=8, eos_id=eos)
        rc = eng.submit(pc, max_new=4)
        assert eng.queue_depth == 2          # admission happens in step()
        eng.step()
        assert rc.status == "queued"         # no slot until A's EOS
        eng.run_until_idle()
        assert ra2.finish_reason == "eos"
        assert ra2.tokens == ra.tokens[:idx + 1]  # stops AT the eos
        assert rc.slot == 0 and rc.finish_reason == "max_tokens"
        want_c = np.asarray(transformer.generate(
            PARAMS, jnp.asarray(pc[None]), CFG, max_new=4))[0]
        np.testing.assert_array_equal(rc.output, want_c)
        assert eng.pool.idle

    def test_submit_guards(self, kind, rng):
        eng = _engine(kind, cache_len=16)
        with pytest.raises(ValueError, match="exceed cache_len"):
            eng.submit(rng.randint(0, 40, 8), max_new=16)
        with pytest.raises(ValueError, match="max_new"):
            eng.submit(rng.randint(0, 40, 4), max_new=0)
        with pytest.raises(ValueError, match="empty prompt"):
            eng.submit(np.zeros(0, np.int32), max_new=2)
        with pytest.raises(ValueError, match="tier"):
            eng.submit(rng.randint(0, 40, 4), max_new=2, tier="gold")
        eng.set_tenant_budget("acme", 8)
        with pytest.raises(ValueError, match="budget"):
            eng.submit(rng.randint(0, 40, 6), max_new=4, tenant="acme")
        # counted, never queued; a prompt longer than one chunk is no
        # refusal (chunked prefill)
        assert eng.queue_depth == 0
        assert eng.metrics.get("engine_requests_rejected_total").value(
            reason="exceeds_cache") == 1
        r = eng.submit(rng.randint(0, 40, 12), max_new=2)
        eng.run_until_idle()
        assert r.finish_reason == "max_tokens"

    def test_unseeded_engines_differ(self, kind, rng):
        """seed=None engines must not replay one sampling stream."""
        prompt = rng.randint(0, 40, 5).astype(np.int32)
        outs = []
        for _ in range(2):
            eng = _engine(kind, seed=None)
            r = eng.submit(prompt, max_new=12, temperature=100.0)
            eng.run_until_idle()
            outs.append(list(r.tokens))
        assert outs[0] != outs[1]


class TestEngineObservability:
    def test_metrics_and_health_endpoint(self, rng):
        import json as _json
        import urllib.request
        eng = _engine()
        for n in (5, 9, 3):
            eng.submit(rng.randint(0, 40, n).astype(np.int32), max_new=4)
        eng.run_until_idle()
        assert eng.metrics.get("engine_tokens_total").value() == 12
        assert eng.metrics.get(
            "engine_ttft_seconds").snapshot()["count"] == 3
        assert eng.metrics.get(
            "engine_requests_completed_total").value(
                reason="max_tokens") == 3
        assert eng.metrics.get("engine_slots_active").value() == 0
        text = eng.metrics_text()
        assert "# TYPE engine_queue_wait_seconds histogram" in text
        assert "engine_request_tokens_per_sec_bucket" in text
        http = eng.serve()
        try:
            health = _json.loads(urllib.request.urlopen(
                http.url + "/healthz", timeout=5).read())
            assert health["status"] == "ok"
            assert health["completed"] == 3 and health["tokens"] == 12
            scraped = urllib.request.urlopen(
                http.url + "/metrics", timeout=5).read().decode()
            assert "engine_tokens_total 12" in scraped
        finally:
            http.close()


class TestServingBenchSmoke:
    def test_bench_smoke_engine_beats_nothing_but_runs(self):
        """Tier-1 exercise of the full bench path (--smoke): both
        variants (engine / lockstep) produce sane numbers on a
        shared-prefix + long-prompt-adversarial trace and the compile
        invariants (asserted inside the runners) hold."""
        import importlib.util
        import os
        spec = importlib.util.spec_from_file_location(
            "serving_bench_under_test",
            os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))),
                "benchmarks", "serving_bench.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        trace_out = os.path.join(os.environ.get("TMPDIR", "/tmp"),
                                 f"req_trace_{os.getpid()}.json")
        try:
            results = mod.main(["--smoke", f"--trace-out={trace_out}"])
        finally:
            if os.path.exists(trace_out):
                os.remove(trace_out)
        # throughput phase: the 6 Poisson requests; latency phase adds
        # 1 adversarial long prompt
        tp, lat = results["throughput"], results["latency"]
        assert tp["engine_paged"]["requests"] == 6
        assert lat["engine_paged"]["requests"] == 7
        for phase in (tp, lat):
            assert phase["engine_paged"]["tokens"] == \
                phase["lockstep"]["tokens"]
            assert phase["engine_paged"]["tokens_per_sec"] > 0
            assert phase["engine_paged"]["compiles"]["decode"] == 1
            # the shared-prefix half of the trace hit the prefix cache
            assert phase["engine_paged"]["prefix_hit_blocks"] >= 1
            assert phase["engine_paged"]["blocks_in_use_peak"] <= \
                phase["engine_paged"]["blocks_total"]
        # flash-decode-era fields: decode MFU reported per engine, the
        # int8 variant rode the throughput phase token-for-token, and
        # the interpret-mode kernel matched the XLA engine's ids
        assert tp["engine_paged"]["decode_mfu"] is not None
        assert tp["engine_paged_int8"]["tokens"] == \
            tp["engine_paged"]["tokens"]
        assert results["serving_int8_speedup"] > 0
        assert results["pallas"]["interpret_check_ok"] is True
        # KV-quantization era fields: the int8-KV pool variant rode
        # the throughput phase token-for-token at ~1/3 the bytes, the
        # quantized interpret check (fused dequant, decode + chunked
        # prefill) held, capacity shows >= 2x slots at equal HBM, and
        # the cold-prefill / quality scoreboards materialized
        assert tp["engine_paged_kv8"]["tokens"] == \
            tp["engine_paged"]["tokens"]
        assert tp["engine_paged_kv8"]["kv_dtype"] == "int8"
        assert tp["engine_paged_kv8"]["kv_bytes_per_token"] < \
            tp["engine_paged"]["kv_bytes_per_token"]
        assert results["serving_kv8_speedup"] > 0
        assert results["pallas"]["interpret_check_kv8_ok"] is True
        cap = results["capacity"]
        assert cap["slots_int8_ge_2x_fp32"] is True
        assert cap["slots_at_equal_hbm_int8"] >= \
            2 * cap["slots_at_equal_hbm_fp32"]
        assert cap["slots_at_equal_hbm_int4"] >= \
            cap["slots_at_equal_hbm_int8"]
        assert results["cold_prefill"]["ttft_p50_cold_ms"] > 0
        q = results["quality"]
        assert 0 < q["kv_int8_rel_l2"] < q["kv_int8_rel_l2_budget"]
        assert 0 < q["kv_int4_rel_l2"] < q["kv_int4_rel_l2_budget"]
        # per-request attribution replay: every request attributed
        # (the joined-lifecycle invariant is asserted INSIDE the bench
        # when --trace-out is given — reaching here means it held)
        attr = results["attribution"]
        assert attr["requests"] == 7
        assert len(attr["slowest_by_ttft"]) == 7
        comps = attr["slowest_by_ttft"][0]["attribution"]["components"]
        assert set(comps) == {"queue_wait_s", "prefill_own_s",
                              "prefill_stall_s", "decode_s"}
        assert attr["victims"]["count"] >= 1
        assert attr["victims"]["adversary_prompt_tokens"] == 56
        # multi-tenant + spec-decode era fields: both phases ran under
        # --smoke (the tiered/FIFO A/B completed leak-free with both
        # tiers represented, and the spec phase's bitwise-greedy +
        # compile-discipline asserts — checked INSIDE the phase —
        # held; the speedup/separation CLAIMS are the full run's)
        mt = results["multitenant"]
        assert mt["tiered"]["requests_latency"] >= 1
        assert mt["tiered"]["requests_batch"] >= 1
        assert mt["fifo"]["tokens_per_sec"] > 0
        sd = results["spec_decode"]
        assert sd["greedy_bitwise_ok"] is True
        assert sd["acceptance_rate"] is not None
        assert sd["spec_tokens_per_sec"] > 0
        assert results["spec_decode_speedup"] > 0
        # serving-fleet era fields: the router A/B ran under --smoke
        # with zero lost requests and the P/D disaggregation bitwise
        # check (asserted INSIDE the phase) held; the goodput /
        # victim-TTFT CLAIMS are the dedicated --fleet run's
        fl = results["fleet"]
        assert fl["all_requests_completed"] is True
        assert fl["pd_bitwise_ok"] is True
        assert fl["fleet"]["requeued"] == 0
        assert fl["fleet"]["tokens_per_sec"] > 0
        assert fl["pd_blocks_shipped"] >= 1

    def test_bench_smoke_fleet_chaos_phase(self):
        """Tier-1 exercise of the control-plane chaos path (--smoke
        --fleet-chaos): the kill fires at the peak, the controller
        heals the fleet back to full capacity, and every admitted
        request completes. The TTFT-band / shed / rewarm CLAIMS are
        the dedicated full-size run's (the fleet sentinel family)."""
        import importlib.util
        import os
        spec = importlib.util.spec_from_file_location(
            "serving_bench_chaos_under_test",
            os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))),
                "benchmarks", "serving_bench.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        results = mod.main(["--smoke", "--fleet-chaos"])
        fc = results["fleet_chaos"]
        assert fc["controlled"]["killed_replica"] is not None
        assert fc["healed_capacity_frac"] == 1.0
        assert fc["recovery_s"] is not None and fc["recovery_s"] > 0
        assert fc["all_admitted_completed"] is True
        assert fc["controlled"]["completed"] == \
            fc["controlled"]["requests"]
        assert fc["static"]["completed"] == fc["static"]["requests"]
