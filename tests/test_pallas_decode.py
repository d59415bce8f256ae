"""Flash-decode Pallas kernel over the paged KV pool + fused sampling
epilogue + the PADDLE_TPU_PALLAS dispatch policy + int8-weight serving.

Contracts (ISSUE 10):
- interpret-mode kernel bitwise-identical to the XLA paged path on
  aligned fp32 shapes, page-scramble invariance included;
- tolerance-bounded under bf16;
- fused-sampling ids matching serving/sampling.sample_tokens semantics
  (greedy + tie convention exact, top-k SET exact, categorical matching
  in distribution);
- engine output with q8 params exact vs the dequantized reference and
  logits within the documented q8 bound of fp32 (global rel-L2, the
  PR-5 deflake recipe);
- the jitted int8 decode HLO contains no loop-invariant fp32 weight
  materialization (the anti-hoist defenses hold);
- the engine's compile-count invariant survives the Pallas path.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.io import lm_serving
from paddle_tpu.models import transformer
from paddle_tpu.observe.compile_tracker import CompileTracker
from paddle_tpu.ops.pallas import decode as fd
from paddle_tpu.ops.pallas import policy
from paddle_tpu.serving import PagedDecodeEngine, sampling

CFG = transformer.TransformerConfig(
    vocab=40, d_model=16, n_heads=2, n_kv_heads=1, n_layers=2, d_ff=32,
    max_len=64, dtype=jnp.float32, use_rope=True)
CFG_ABS = transformer.TransformerConfig(
    vocab=40, d_model=16, n_heads=2, n_layers=2, d_ff=32,
    max_len=64, dtype=jnp.float32, use_rope=False)
PARAMS = transformer.init_params(jax.random.PRNGKey(0), CFG)

BS = 8


def _pool_from_arena(cache, cfg):
    """Arena [L, B, T, Hkv, Dh] -> head-major flat pool [L, Hkv, M, Dh]
    with identity paging."""
    L, B, T = cache["k"].shape[:3]
    pool = {k: jnp.moveaxis(jnp.reshape(
        v, (L, B * T, cfg.kv_heads, cfg.head_dim)), 1, 2)
        for k, v in cache.items()}
    pages = np.arange(B * (T // BS), dtype=np.int32).reshape(B, T // BS)
    return pool, jnp.asarray(pages)


def _scramble(pool, pages, rng):
    """Permute physical blocks (the pool position axis is axis 2 at
    the head-major layout), remap the page table — same logical
    content at different physical placement."""
    M = pool["k"].shape[2]
    nb = M // BS
    perm = rng.permutation(nb).astype(np.int32)      # old block i -> perm[i]
    gidx = np.empty(M, np.int64)
    for i in range(nb):
        gidx[perm[i] * BS:(perm[i] + 1) * BS] = np.arange(
            i * BS, (i + 1) * BS)
    pool2 = {k: jnp.asarray(np.asarray(v)[:, :, gidx])
             for k, v in pool.items()}
    pages2 = jnp.asarray(perm[np.asarray(pages)])
    return pool2, pages2


class TestPallasPolicy:
    """One knob, tested precedence: explicit arg > env > auto."""

    def test_auto_resolves_by_backend(self, monkeypatch):
        monkeypatch.delenv("PADDLE_TPU_PALLAS", raising=False)
        want = "on" if jax.default_backend() == "tpu" else "off"
        assert policy.pallas_mode(None) == want
        assert policy.pallas_mode("auto") == want

    def test_env_over_auto(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_PALLAS", "interpret")
        assert policy.pallas_mode(None) == "interpret"

    def test_explicit_over_env(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_PALLAS", "off")
        assert policy.pallas_mode("interpret") == "interpret"
        assert policy.pallas_mode("on") == "on"

    def test_invalid_value_raises(self, monkeypatch):
        with pytest.raises(ValueError, match="PADDLE_TPU_PALLAS"):
            policy.pallas_mode("fast")
        monkeypatch.setenv("PADDLE_TPU_PALLAS", "yes")
        with pytest.raises(ValueError, match="PADDLE_TPU_PALLAS"):
            policy.pallas_mode(None)

    def test_flash_attention_routes_through_policy(self, monkeypatch,
                                                   rng):
        """attention.py's old ad-hoc off-TPU check is gone: the env
        alone flips the public entry between the jnp reference and the
        (interpret) kernel; an explicit ``interpret`` arg beats the
        env."""
        from paddle_tpu.ops.pallas import attention as fa
        from paddle_tpu.parallel import ring
        q = jnp.asarray(rng.randn(1, 32, 2, 8).astype(np.float32))
        ref = ring.full_attention(q, q, q, causal=True)

        class _Sentinel(Exception):
            pass

        def boom(*a, **k):
            raise _Sentinel

        monkeypatch.setattr(fa, "_reference", boom)
        monkeypatch.setenv("PADDLE_TPU_PALLAS", "off")
        with pytest.raises(_Sentinel):
            fa.flash_attention(q, q, q, causal=True)
        # env turns the kernel on; the reference is never consulted
        monkeypatch.setenv("PADDLE_TPU_PALLAS", "interpret")
        out = fa.flash_attention(q, q, q, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        # explicit arg wins over the env
        monkeypatch.setenv("PADDLE_TPU_PALLAS", "off")
        out = fa.flash_attention(q, q, q, causal=True, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


class TestFlashDecodeKernel:
    @pytest.mark.parametrize("cfg", [CFG, CFG_ABS],
                             ids=["rope", "learned-pos"])
    def test_bitwise_vs_xla_paged(self, cfg, rng):
        """Aligned fp32 shapes: the interpret-mode kernel's decode step
        reproduces the XLA paged path's logits AND written cache
        bitwise (inactive rows included)."""
        params = transformer.init_params(jax.random.PRNGKey(0), cfg)
        B, Tp, T = 3, 6, 32
        prompt = jnp.asarray(rng.randint(0, 40, (B, Tp)), jnp.int32)
        logits, cache = transformer.prefill(params, prompt, cfg, T)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        pos = jnp.asarray([6, 3, 9], jnp.int32)
        active = jnp.asarray([True, False, True])
        pool, pages = _pool_from_arena(cache, cfg)
        l_xla, c_xla = transformer.decode_step_paged(
            params, pool, tok, pos, active, pages, cfg, block_size=BS,
            pallas="off")
        l_pal, c_pal = transformer.decode_step_paged(
            params, pool, tok, pos, active, pages, cfg, block_size=BS,
            pallas="interpret")
        np.testing.assert_array_equal(np.asarray(l_xla),
                                      np.asarray(l_pal))
        for leaf in ("k", "v"):
            np.testing.assert_array_equal(np.asarray(c_xla[leaf]),
                                          np.asarray(c_pal[leaf]))

    def test_page_scramble_invariance(self, rng):
        """Physical placement is invisible to the kernel: scrambled
        blocks + remapped page table decode bitwise identically, and
        still bitwise the XLA path on the same scrambled pool."""
        B, Tp, T = 2, 6, 32
        prompt = jnp.asarray(rng.randint(0, 40, (B, Tp)), jnp.int32)
        logits, cache = transformer.prefill(PARAMS, prompt, CFG, T)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        pos = jnp.full((B,), Tp, jnp.int32)
        active = jnp.ones((B,), bool)
        pool, pages = _pool_from_arena(cache, CFG)
        l_id, _ = transformer.decode_step_paged(
            PARAMS, pool, tok, pos, active, pages, CFG, block_size=BS,
            pallas="interpret")
        pool2, pages2 = _scramble(pool, pages, rng)
        l_sc, _ = transformer.decode_step_paged(
            PARAMS, pool2, tok, pos, active, pages2, CFG, block_size=BS,
            pallas="interpret")
        np.testing.assert_array_equal(np.asarray(l_id), np.asarray(l_sc))
        l_xla, _ = transformer.decode_step_paged(
            PARAMS, pool2, tok, pos, active, pages2, CFG, block_size=BS,
            pallas="off")
        np.testing.assert_array_equal(np.asarray(l_sc),
                                      np.asarray(l_xla))

    def test_bf16_tolerance(self, rng):
        """bf16 pool: kernel vs XLA path within bf16 rounding (both
        accumulate fp32; the pool read rounds once per element)."""
        cfg = transformer.TransformerConfig(
            vocab=40, d_model=16, n_heads=2, n_kv_heads=1, n_layers=2,
            d_ff=32, max_len=64, dtype=jnp.bfloat16, use_rope=True)
        params = transformer.init_params(jax.random.PRNGKey(0), cfg)
        B, Tp, T = 2, 6, 32
        prompt = jnp.asarray(rng.randint(0, 40, (B, Tp)), jnp.int32)
        logits, cache = transformer.prefill(params, prompt, cfg, T)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        pos = jnp.full((B,), Tp, jnp.int32)
        active = jnp.ones((B,), bool)
        pool, pages = _pool_from_arena(cache, cfg)
        l_xla, _ = transformer.decode_step_paged(
            params, pool, tok, pos, active, pages, cfg, block_size=BS,
            pallas="off")
        l_pal, _ = transformer.decode_step_paged(
            params, pool, tok, pos, active, pages, cfg, block_size=BS,
            pallas="interpret")
        np.testing.assert_allclose(np.asarray(l_xla, np.float32),
                                   np.asarray(l_pal, np.float32),
                                   rtol=2e-2, atol=2e-2)

    def test_kernel_direct_tile_sweep(self, rng):
        """The raw kernel entry over every legal tile returns the same
        values (tile is a scheduling knob — pages streamed per grid
        step — not a numerics knob)."""
        B, Hkv, G, Dh, P = 2, 2, 2, 8, 4
        M = 2 * B * P * BS
        q = jnp.asarray(rng.randn(B, Hkv, G, Dh).astype(np.float32))
        k = jnp.asarray(rng.randn(Hkv, M, Dh).astype(np.float32))
        v = jnp.asarray(rng.randn(Hkv, M, Dh).astype(np.float32))
        pages = jnp.asarray(rng.permutation(M // BS)[:B * P]
                            .reshape(B, P).astype(np.int32))
        pos = jnp.asarray([13, 30], jnp.int32)
        outs = [np.asarray(fd.flash_decode_attention(
            q, k, v, pages, pos, block_size=BS, tile=t, interpret=True))
            for t in (1, 2, 4)]
        np.testing.assert_array_equal(outs[0], outs[1])
        np.testing.assert_array_equal(outs[0], outs[2])
        with pytest.raises(ValueError, match="tile"):
            fd.flash_decode_attention(q, k, v, pages, pos,
                                      block_size=BS, tile=3,
                                      interpret=True)

    def test_tile_selection_and_budget(self):
        # analytic default: pow2 divisor of P, <= 256 rows per step
        assert fd.select_decode_tile(16, 16, 64, jnp.bfloat16) == 16
        assert fd.select_decode_tile(128, 16, 64, jnp.bfloat16) == 16
        assert fd.select_decode_tile(6, 16, 64, jnp.bfloat16) == 2
        # measured table is keyed by POOL LAYOUT first (stale
        # slot-major sweep entries can never match) and wins only when
        # its advisory block size matches
        key = (fd.POOL_LAYOUT, 1 << 11, 64, "bfloat16")
        fd.MEASURED_DECODE[key] = (16, 4)
        try:
            assert fd.select_decode_tile(128, 16, 64, jnp.bfloat16) == 4
            assert fd.select_decode_tile(128, 32, 64, jnp.bfloat16) != 4
        finally:
            del fd.MEASURED_DECODE[key]
        # a pre-relayout-style key (no layout token) is dead weight
        fd.MEASURED_DECODE[(1 << 11, 64, "bfloat16")] = (16, 4)
        try:
            assert fd.select_decode_tile(128, 16, 64,
                                         jnp.bfloat16) == 16
        finally:
            del fd.MEASURED_DECODE[(1 << 11, 64, "bfloat16")]
        # budget: scalar-prefetched streaming made the working set
        # independent of the pool size M (only the slot's own span
        # lives in scratch) — a huge pool behind a serving-sized span
        # fits; a span whose V scratch alone exceeds VMEM does not
        from paddle_tpu.ops.pallas import policy
        with policy.compile_target("TPU v5 lite"):
            small = fd.decode_vmem_bytes(8 * 2048, 16, 128, 4, 128, 2)
            # a serving-sized span asks for no more than the scoped
            # default; the limit is never set below it
            assert policy.vmem_limit_bytes(small, "t") == \
                policy.SCOPED_VMEM_DEFAULT_BYTES
            big = fd.decode_vmem_bytes(512 * 8192, 64, 128, 8, 256, 4)
            assert big > policy.SCOPED_VMEM_DEFAULT_BYTES // 2
            assert policy.vmem_limit_bytes(big, "t") == 2 * big
            huge = fd.decode_vmem_bytes(1 << 30, 1 << 12, 128, 8, 512,
                                        4)
            with pytest.raises(ValueError, match="planning budget"):
                policy.vmem_limit_bytes(huge, "t")


class TestFusedSample:
    def test_greedy_rows_exact_and_tie_first_index(self, rng):
        logits = rng.randn(3, 11).astype(np.float32)
        logits[1, 2] = logits[1, 7] = logits[1].max() + 1.0   # tie
        lg = jnp.asarray(logits)
        temp = jnp.zeros((3,), jnp.float32)
        topk = jnp.asarray([0, 4, 11], jnp.int32)
        ids = np.asarray(fd.fused_sample(lg, np.int32(5), temp, topk,
                                         interpret=True))
        ref = np.asarray(sampling.sample_tokens(
            lg, jax.random.PRNGKey(5), temp, topk))
        np.testing.assert_array_equal(ids, ref)
        assert ids[1] == 2                       # first-index tie win

    def test_topk_membership_and_disable(self, rng):
        """Sampled ids always land in the exact top-k SET (ties at the
        threshold included); k<=0 and k>=V disable filtering."""
        logits = rng.randn(4, 13).astype(np.float32)
        logits[2, 5] = logits[2, 8]              # tie at the threshold
        lg = jnp.asarray(logits)
        temp = jnp.full((4,), 0.7, jnp.float32)
        topk = jnp.asarray([3, 0, 3, 50], jnp.int32)
        f = jax.jit(lambda s: fd.fused_sample(lg, s, temp, topk,
                                              interpret=True))
        keep = []
        for b, k in enumerate((3, 0, 3, 50)):
            if k <= 0 or k >= 13:
                keep.append(set(range(13)))
            else:
                kth = np.sort(logits[b])[::-1][k - 1]
                keep.append({i for i in range(13)
                             if logits[b, i] >= kth})
        for s in range(64):
            ids = np.asarray(f(jnp.asarray(s, jnp.int32)))
            for b in range(4):
                assert int(ids[b]) in keep[b], (b, s, ids[b])

    def test_categorical_matches_distribution(self, rng):
        """Temperature sampling follows softmax(logits/t) — the hash-
        Gumbel stream differs from jax.random's per id, so the contract
        is the distribution (deterministic seeds, fixed tolerance)."""
        lg = jnp.asarray(rng.randn(1, 5).astype(np.float32))
        temp = jnp.full((1,), 0.8, jnp.float32)
        topk = jnp.zeros((1,), jnp.int32)
        f = jax.jit(lambda s: fd.fused_sample(lg, s, temp, topk,
                                              interpret=True))
        counts = np.zeros(5)
        n = 1500
        for s in range(n):
            counts[int(np.asarray(f(jnp.asarray(s, jnp.int32)))[0])] += 1
        probs = np.asarray(jax.nn.softmax(np.asarray(lg[0]) / 0.8))
        np.testing.assert_allclose(counts / n, probs, atol=0.05)


def _paged(pallas=None, params=PARAMS, **kw):
    kw.setdefault("batch", 2)
    kw.setdefault("cache_len", 32)
    kw.setdefault("block_size", 8)
    kw.setdefault("chunk_tokens", 8)
    return PagedDecodeEngine.from_params(
        params, CFG, seed=0, tracker=CompileTracker(), pallas=pallas,
        **kw)


class TestEnginePallas:
    def test_engine_outputs_match_generate_and_xla(self, rng):
        """Greedy paged-engine output through the interpret-mode kernel
        + fused epilogue == transformer.generate == the XLA-path
        engine, mixed lengths, chunked prefill included; the
        one-decode-program invariant survives."""
        prompts = [rng.randint(0, 40, n).astype(np.int32)
                   for n in (5, 9, 3, 20)]
        eng_pal = _paged(pallas="interpret")
        eng_xla = _paged(pallas="off")
        outs = {}
        for name, eng in (("pal", eng_pal), ("xla", eng_xla)):
            reqs = [eng.submit(p, max_new=6) for p in prompts]
            eng.run_until_idle()
            outs[name] = [r.output for r in reqs]
        for p, a, b in zip(prompts, outs["pal"], outs["xla"]):
            want = np.asarray(transformer.generate(
                PARAMS, jnp.asarray(p[None]), CFG, max_new=6))[0]
            np.testing.assert_array_equal(a, want)
            np.testing.assert_array_equal(b, want)
        assert eng_pal.compile_counts()["decode"] == 1
        assert eng_pal.pallas_mode == "interpret"
        assert eng_pal.health()["pallas"] == "interpret"

    def test_decode_mfu_reported(self, rng):
        """The engine knows its decode FLOPs (lowered cost analysis)
        and reports a positive mean decode MFU after a run — the
        serving_bench scoreboard field."""
        eng = _paged(pallas="off")
        assert eng.decode_flops and eng.decode_flops > 0
        eng.submit(rng.randint(0, 40, 5).astype(np.int32), max_new=4)
        eng.run_until_idle()
        mfu = eng.decode_mfu()
        assert mfu is not None and mfu > 0
        # the per-step gauge and the /healthz field went (PR 24): XLA's
        # cost model over host time is no share of a chip
        assert "decode_mfu" not in eng.health()
        assert "engine_decode_mfu" not in eng.metrics_text()


class TestNoSilentFallback:
    """``on`` — and ``auto`` on a TPU — place the compiled kernels or
    raise; only ``off`` selects the XLA path. Nothing degrades."""

    def test_on_mode_raises_off_tpu(self):
        """Off-TPU there is no chip to take a compiled kernel: the
        engine refuses at construction (its decode program traces
        there), naming the way out — it used to serve XLA silently."""
        if jax.default_backend() == "tpu":
            pytest.skip("off-TPU refusal")
        with pytest.raises(ValueError, match="block_size 8 is not a "
                                             "multiple of 128"):
            _paged(pallas="on")
        # at a block size the compiler takes, the missing chip is the
        # refusal
        kv = jnp.zeros((1, 128, 8), jnp.float32)
        with pytest.raises(ValueError, match="no VMEM figure for "
                                             "device kind"):
            fd.flash_decode_attention(
                jnp.zeros((1, 1, 2, 8), jnp.float32), kv, kv,
                jnp.zeros((1, 1), jnp.int32), jnp.zeros(1, jnp.int32),
                block_size=128)

    def test_auto_on_tpu_raises_instead_of_degrading(self, monkeypatch):
        """``auto`` resolves against the backend; with a TPU reported
        it is ``on`` — same refusal, no quiet XLA engine."""
        monkeypatch.delenv("PADDLE_TPU_PALLAS", raising=False)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert policy.pallas_mode(None) == "on"
        with pytest.raises(ValueError, match="multiple of 128"):
            _paged(pallas=None)

    def test_unknown_device_kind_is_an_error(self):
        with policy.compile_target("TPU v99"):
            with pytest.raises(ValueError, match="TPU v99"):
                policy.vmem_capacity_bytes()
        with policy.compile_target("TPU v5 lite"):
            assert policy.vmem_capacity_bytes() == 128 << 20

    def test_sampler_and_flash_attention_do_not_fall_back(self, rng):
        """The sampling epilogue and the training flash kernel place
        the compiled kernel under ``on`` too — off-TPU that is a
        refused compile, never the XLA sampler / jnp reference."""
        from paddle_tpu.ops.pallas import flash_attention
        from paddle_tpu.serving import sampling
        if jax.default_backend() == "tpu":
            pytest.skip("off-TPU refusal")
        tail = sampling._epilogue("on")
        with pytest.raises(Exception, match="[Ii]nterpret|TPU"):
            jax.jit(tail)(jnp.zeros((2, 40)), jnp.int32(0),
                          jnp.zeros(2), jnp.zeros(2, jnp.int32)
                          ).block_until_ready()
        q = jnp.asarray(rng.randn(1, 16, 2, 8), jnp.float32)
        with pytest.raises(ValueError, match="no VMEM figure"):
            flash_attention(q, q, q, interpret=False)


class TestKernelPathRecord:
    """The engine reports, per compiled program, the path each kernel
    site actually placed — recorded at trace time, where placement
    happens."""

    def test_paths_per_program_by_mode(self, rng):
        for mode, want in (("interpret", policy.PATH_INTERPRET),
                           ("off", policy.PATH_XLA)):
            eng = _paged(pallas=mode)
            # construction traced the decode program (its FLOPs)
            assert eng.kernel_paths["decode"] == {
                "attention": want, "sampler": want}
            eng.submit(rng.randint(0, 40, 20).astype(np.int32),
                       max_new=3)
            eng.run_until_idle()
            prefills = {k: v for k, v in eng.kernel_paths.items()
                        if k.startswith("prefill_")}
            assert len(prefills) == eng.compile_counts()["prefill"]
            for rec in prefills.values():
                assert rec == {"attention": want, "span_write": want,
                               "sampler": want}
            assert eng.health()["kernel_paths"] == eng.kernel_paths

    def test_precompile_covers_the_chunk_grid(self, rng):
        """precompile() runs every (bucket, span) program + decode on
        an idle engine without disturbing what a request reads:
        outputs equal an engine that compiled lazily, and traffic
        afterwards compiles nothing new."""
        prompts = [rng.randint(0, 40, n).astype(np.int32)
                   for n in (5, 20)]
        lazy, warm = _paged(pallas="off"), _paged(pallas="off")
        counts = warm.precompile()
        spans = warm.cache_len // warm.chunk_tokens
        assert counts == {"prefill": spans * len(warm.buckets),
                          "decode": 1}
        outs = []
        for eng in (lazy, warm):
            reqs = [eng.submit(p, max_new=5) for p in prompts]
            eng.run_until_idle()
            outs.append([r.output.tolist() for r in reqs])
        assert outs[0] == outs[1]
        assert warm.compile_counts() == counts
        warm.submit(prompts[0], max_new=20)
        warm.step()
        with pytest.raises(RuntimeError, match="idle"):
            warm.precompile()


class TestInt8Serving:
    def test_engine_q8_exact_vs_dequantized_reference(self, rng):
        """The in-scan dequant computes with bitwise the SAME live
        weights dequantize_tree would materialize, so the q8 engine's
        greedy output equals generate() over the dequantized tree
        exactly — the int8 path changes WHERE dequant happens, never
        the values."""
        from paddle_tpu.ops import q8 as ops_q8
        qp = lm_serving.quantize_lm_params(PARAMS)
        live = jax.tree_util.tree_map(
            lambda n: jnp.asarray(ops_q8.dequantize_weight(n))
            if ops_q8.is_quantized_weight(n) else n,
            qp, is_leaf=ops_q8.is_quantized_weight)
        eng = _paged(params=qp)
        prompts = [rng.randint(0, 40, n).astype(np.int32)
                   for n in (5, 9)]
        reqs = [eng.submit(p, max_new=6) for p in prompts]
        eng.run_until_idle()
        for p, r in zip(prompts, reqs):
            want = np.asarray(transformer.generate(
                live, jnp.asarray(p[None]), CFG, max_new=6))[0]
            np.testing.assert_array_equal(r.output, want)

    def test_q8_logits_within_documented_bound(self, rng):
        """Global rel-L2 of the q8 decode logits vs fp32 (PR-5 deflake
        recipe: a GLOBAL metric, not per-element): per-channel
        symmetric rounding injects <= 0.5/127 relative weight noise;
        through 2·n_layers matmuls + the vocab head that compounds to
        ~(2L+2)·0.5/127 ≈ 2.4% here — budget 5% leaves 2x slack
        without ever excusing a wrong-scale bug (which lands >> 10%)."""
        B, Tp, T = 3, 6, 32
        prompt = jnp.asarray(rng.randint(0, 40, (B, Tp)), jnp.int32)
        logits, cache = transformer.prefill(PARAMS, prompt, CFG, T)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        pos = jnp.full((B,), Tp, jnp.int32)
        active = jnp.ones((B,), bool)
        pool, pages = _pool_from_arena(cache, CFG)
        l_fp, _ = transformer.decode_step_paged(
            PARAMS, pool, tok, pos, active, pages, CFG, block_size=BS,
            pallas="off")
        qp = lm_serving.quantize_lm_params(PARAMS)
        l_q8, _ = transformer.decode_step_paged(
            qp, pool, tok, pos, active, pages, CFG, block_size=BS,
            pallas="off")
        a, b = np.asarray(l_fp), np.asarray(l_q8)
        rel = np.linalg.norm(a - b) / np.linalg.norm(a)
        assert rel < 0.05, rel

    def test_q8_pallas_bitwise_matches_q8_xla(self, rng):
        """int8 weights and the flash-decode kernel compose: same
        logits bitwise as the q8 XLA path (fp32 aligned shapes)."""
        B, Tp, T = 2, 6, 32
        prompt = jnp.asarray(rng.randint(0, 40, (B, Tp)), jnp.int32)
        _, cache = transformer.prefill(PARAMS, prompt, CFG, T)
        tok = jnp.zeros((B,), jnp.int32)
        pos = jnp.full((B,), Tp, jnp.int32)
        active = jnp.ones((B,), bool)
        pool, pages = _pool_from_arena(cache, CFG)
        qp = lm_serving.quantize_lm_params(PARAMS)
        l_xla, _ = transformer.decode_step_paged(
            qp, pool, tok, pos, active, pages, CFG, block_size=BS,
            pallas="off")
        l_pal, _ = transformer.decode_step_paged(
            qp, pool, tok, pos, active, pages, CFG, block_size=BS,
            pallas="interpret")
        np.testing.assert_array_equal(np.asarray(l_xla),
                                      np.asarray(l_pal))

    def test_no_loop_invariant_fp32_weight_materialization(self):
        """The optimized decode HLO must carry the block weights as the
        int8 stack and dequantize per-layer INSIDE the scan: any
        f32[L, ...] tensor of a stacked weight shape would mean XLA
        hoisted a full fp32 materialization (4-byte reads per token —
        the regression the carry/barrier/loop-variant-scale defenses
        exist to prevent)."""
        qp = lm_serving.quantize_lm_params(PARAMS)
        _, decode_fn = sampling.paged_step_fns(CFG, BS, pallas="off")
        B, P = 2, 4
        pool = transformer.init_block_pool(CFG, 8, BS)
        args = (qp, pool, np.zeros(B, np.int32), np.zeros(B, np.int32),
                np.zeros(B, bool), np.zeros((B, P), np.int32),
                np.zeros(B, np.float32), np.zeros(B, np.int32),
                np.int32(0))
        hlo = jax.jit(decode_fn).lower(*args).compile().as_text()
        L, D = CFG.n_layers, CFG.d_model
        E = D + 2 * CFG.kv_heads * CFG.head_dim
        F = CFG.d_ff
        for shape in (f"f32[{L},{D},{E}]", f"f32[{L},{D},{F}]",
                      f"f32[{L},{F},{D}]", f"f32[{L},{D},{D}]"):
            assert shape not in hlo, (
                f"full-stack fp32 weights {shape} materialized — the "
                f"in-scan dequant was hoisted")
        # the int8 stack must actually ride the program
        assert f"s8[{L},{D},{E}]" in hlo
