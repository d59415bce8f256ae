"""Chunked-prefill Pallas kernels over the paged KV pool.

Contracts (ISSUE 12, mirroring how flash_decode_attention was pinned):
- interpret-mode chunk attention + span-write kernels are BITWISE the
  XLA chunk path on aligned fp32 shapes — logits and written pool, cold
  (ctx = 0) and contextful chunks, scrambled placement included;
- quantized pools compose: fused context dequant + quantized span
  writes stay bitwise the XLA quantized path;
- the span-write kernel's masked rows keep the pool's old bytes (the
  RMW contract the XLA fallback expresses as slice + where + update);
- tile is a scheduling knob, not a numerics knob; selection consults
  MEASURED_PREFILL only when its block-size advisory matches;
- the engine's chunk programs ride the kernel path under the policy
  knob with the compile-count invariant intact.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import transformer
from paddle_tpu.observe.compile_tracker import CompileTracker
from paddle_tpu.ops.pallas import prefill as fp
from paddle_tpu.serving import PagedDecodeEngine

CFG = transformer.TransformerConfig(
    vocab=40, d_model=16, n_heads=2, n_kv_heads=1, n_layers=2, d_ff=32,
    max_len=64, dtype=jnp.float32, use_rope=True)
CFG_ABS = transformer.TransformerConfig(
    vocab=40, d_model=16, n_heads=2, n_layers=2, d_ff=32,
    max_len=64, dtype=jnp.float32, use_rope=False)
PARAMS = transformer.init_params(jax.random.PRNGKey(0), CFG)

BS = 8


def _walk(prompt, pages, cfg, params, *, kv_dtype=None, pallas="off",
          chunks=(8, 6)):
    """Chunk-walk ``prompt`` into a fresh 6-block pool at the given
    physical placement; returns (final logits, pool)."""
    pool = transformer.init_block_pool(cfg, 6, BS, kv_dtype=kv_dtype)
    off, lg = 0, None
    for c in chunks:
        bucket = 8 if c <= 8 else 16
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :c] = prompt[off:off + c]
        pv = pages[:off // BS + -(-bucket // BS)]
        lg, pool = transformer.prefill_into_blocks(
            params, pool, jnp.asarray(padded),
            jnp.asarray(c, jnp.int32), jnp.asarray(pv, jnp.int32),
            cfg, block_size=BS, pallas=pallas)
        off += c
    return lg, pool


class TestChunkPrefillKernel:
    @pytest.mark.parametrize("cfg", [CFG, CFG_ABS],
                             ids=["rope", "learned-pos"])
    def test_bitwise_vs_xla_cold_and_contextful(self, cfg, rng):
        """fp32 pool: the interpret kernels reproduce the XLA chunk
        path bitwise — the cold first chunk (no context inputs at
        all), the contextful second chunk (in-kernel page gather), and
        the padded tail's masked span write."""
        params = transformer.init_params(jax.random.PRNGKey(0), cfg)
        prompt = rng.randint(0, 40, 14).astype(np.int32)
        pages = np.asarray([3, 1], np.int32)      # scrambled placement
        lg_x, pool_x = _walk(prompt, pages, cfg, params, pallas="off")
        lg_p, pool_p = _walk(prompt, pages, cfg, params,
                             pallas="interpret")
        np.testing.assert_array_equal(np.asarray(lg_x),
                                      np.asarray(lg_p))
        for leaf in pool_x:
            np.testing.assert_array_equal(np.asarray(pool_x[leaf]),
                                          np.asarray(pool_p[leaf]))

    @pytest.mark.parametrize("kvd", ["int8", "int4"])
    def test_bitwise_vs_xla_quantized(self, kvd, rng):
        """Quantized pools: fused context dequant in the gather loop +
        quantized masked span writes (values AND scale rows) stay
        bitwise the XLA quantized path."""
        prompt = rng.randint(0, 40, 14).astype(np.int32)
        pages = np.asarray([4, 2], np.int32)
        lg_x, pool_x = _walk(prompt, pages, CFG, PARAMS, kv_dtype=kvd,
                             pallas="off")
        lg_p, pool_p = _walk(prompt, pages, CFG, PARAMS, kv_dtype=kvd,
                             pallas="interpret")
        np.testing.assert_array_equal(np.asarray(lg_x),
                                      np.asarray(lg_p))
        for leaf in ("k", "v", "k_scale", "v_scale"):
            np.testing.assert_array_equal(np.asarray(pool_x[leaf]),
                                          np.asarray(pool_p[leaf]))

    def test_span_write_masked_rows_keep_old_bytes(self, rng):
        """The aliased span-write kernel's RMW contract: rows past the
        chunk's valid length write back the span's OLD bytes — pinned
        against a sentinel-filled pool, not just zeros."""
        sentinel = {
            "k": jnp.full((CFG.n_layers, CFG.kv_heads, 6 * BS,
                           CFG.head_dim), 7.5, jnp.float32),
            "v": jnp.full((CFG.n_layers, CFG.kv_heads, 6 * BS,
                           CFG.head_dim), -3.25, jnp.float32)}
        c = 5                                     # bucket 8: 3 padded
        padded = np.zeros((1, 8), np.int32)
        padded[0, :c] = rng.randint(0, 40, c)
        outs = {}
        for mode in ("off", "interpret"):
            _, pool = transformer.prefill_into_blocks(
                PARAMS, dict(sentinel), jnp.asarray(padded),
                jnp.asarray(c, jnp.int32), jnp.asarray([2], jnp.int32),
                CFG, block_size=BS, pallas=mode)
            outs[mode] = pool
        for leaf in ("k", "v"):
            a = np.asarray(outs["off"][leaf])
            b = np.asarray(outs["interpret"][leaf])
            np.testing.assert_array_equal(a, b)
            # padded rows of the written block keep the sentinel
            want = 7.5 if leaf == "k" else -3.25
            np.testing.assert_array_equal(
                b[:, :, 2 * BS + c:3 * BS], want)
            # untouched blocks fully intact
            np.testing.assert_array_equal(b[:, :, :2 * BS], want)
            # valid rows actually changed
            assert not (b[:, :, 2 * BS:2 * BS + c] == want).all()

    def test_span_write_old_bytes_come_from_the_input_block(self):
        """On the chip an aliased OUTPUT block starts as whatever VMEM
        held; only input blocks are copied in. The generic interpreter
        pre-fills outputs from their aliases and hid a kernel that read
        old bytes off the output ref (first v5e run: garbage over every
        padded row, NaN out of decode). The TPU interpreter models the
        hardware: two pages through one program buffer, so a stale
        output block shows the previous page's span."""
        from jax.experimental.pallas import tpu as pltpu
        L, H, M, D = 2, 2, 4 * BS, 8
        pool = {"k": jnp.ones((L, H, M, D)), "v": jnp.full((L, H, M, D),
                                                            2.0)}
        spans = {"k": jnp.full((L, H, 2 * BS, D), 5.0),
                 "v": jnp.full((L, H, 2 * BS, D), 6.0)}
        valid = jnp.arange(2 * BS) < BS + 2      # page 2: 2 valid rows
        out = fp.paged_span_write(
            pool, spans, jnp.asarray([3, 1], jnp.int32), valid,
            block_size=BS, interpret=pltpu.InterpretParams())
        for leaf, old, new in (("k", 1.0, 5.0), ("v", 2.0, 6.0)):
            a = np.asarray(out[leaf])
            np.testing.assert_array_equal(a[:, :, 3 * BS:], new)
            np.testing.assert_array_equal(a[:, :, BS:BS + 2], new)
            np.testing.assert_array_equal(a[:, :, BS + 2:2 * BS], old)
            np.testing.assert_array_equal(a[:, :, :BS], old)

    def test_kernel_direct_tile_sweep(self, rng):
        """flash_chunk_prefill over every legal tile returns identical
        values (tile schedules the gather, never the numerics)."""
        C, Hkv, G, Dh, P_ctx = 8, 2, 2, 8, 4
        M = 2 * P_ctx * BS
        q = jnp.asarray(rng.randn(C, Hkv, G, Dh).astype(np.float32))
        kck = jnp.asarray(rng.randn(C, Hkv, Dh).astype(np.float32))
        vck = jnp.asarray(rng.randn(C, Hkv, Dh).astype(np.float32))
        k = jnp.asarray(rng.randn(Hkv, M, Dh).astype(np.float32))
        v = jnp.asarray(rng.randn(Hkv, M, Dh).astype(np.float32))
        pages = jnp.asarray(rng.permutation(M // BS)[:P_ctx]
                            .astype(np.int32))
        outs = [np.asarray(fp.flash_chunk_prefill(
            q, kck, vck, k, v, pages, block_size=BS, tile=t,
            interpret=True)) for t in (1, 2, 4)]
        np.testing.assert_array_equal(outs[0], outs[1])
        np.testing.assert_array_equal(outs[0], outs[2])
        with pytest.raises(ValueError, match="tile"):
            fp.flash_chunk_prefill(q, kck, vck, k, v, pages,
                                   block_size=BS, tile=3,
                                   interpret=True)

    def test_tile_selection_and_budget(self):
        # analytic default mirrors the decode kernel's rule
        assert fp.select_prefill_tile(0, 16, 64, 64, jnp.float32) == 1
        assert fp.select_prefill_tile(16, 16, 64, 64,
                                      jnp.bfloat16) == 16
        assert fp.select_prefill_tile(6, 16, 64, 64, jnp.bfloat16) == 2
        # measured table is keyed by POOL LAYOUT first and wins only
        # when its advisory block size matches
        key = (fp.POOL_LAYOUT, 1 << 11, 64, 64, "bfloat16")
        fp.MEASURED_PREFILL[key] = (16, 4)
        try:
            assert fp.select_prefill_tile(128, 16, 64, 64,
                                          jnp.bfloat16) == 4
            assert fp.select_prefill_tile(128, 32, 64, 64,
                                          jnp.bfloat16) != 4
        finally:
            del fp.MEASURED_PREFILL[key]
        # a pre-relayout-style key (no layout token) is never consulted
        fp.MEASURED_PREFILL[(1 << 11, 64, 64, "bfloat16")] = (16, 4)
        try:
            assert fp.select_prefill_tile(128, 16, 64, 64,
                                          jnp.bfloat16) == 16
        finally:
            del fp.MEASURED_PREFILL[(1 << 11, 64, 64, "bfloat16")]
        # quantized pools key by their storage name
        key4 = (fp.POOL_LAYOUT, 1 << 11, 64, 64, "int4")
        fp.MEASURED_PREFILL[key4] = (16, 8)
        try:
            assert fp.select_prefill_tile(
                128, 16, 64, 64, jnp.int8, kv_dtype="int4") == 8
        finally:
            del fp.MEASURED_PREFILL[key4]
        # budget: the scalar-prefetched stream made the working set
        # independent of the pool size M (pre-relayout, two whole
        # M-row pool head columns sat in VMEM) — a giant pool behind a
        # serving-sized chunk fits; the score scratch is what binds
        # now, so a huge (chunk x span) product does not
        from paddle_tpu.ops.pallas import policy
        with policy.compile_target("TPU v5 lite"):
            for M in (4 * 2048, 512 * 8192):
                need = fp.prefill_vmem_bytes(M, 2048, 64, 4, 128, 2)
                assert policy.vmem_limit_bytes(need, "t") >= need
            with pytest.raises(ValueError, match="planning budget"):
                policy.vmem_limit_bytes(fp.prefill_vmem_bytes(
                    512 * 8192, 32768, 512, 8, 256, 4), "t")
        # the span-write program holds ONE layer's page: its VMEM does
        # not grow with the layer count, and the lane-padded scale
        # column is what a quantized pool pays
        blk = [((1, 8, 128, 64), jnp.bfloat16)]
        assert fp.span_write_vmem_bytes(blk) == 6 * 8 * 128 * 128 * 2
        assert fp.span_write_vmem_bytes(
            [((1, 8, 128, 1), jnp.float32)]) == 6 * 8 * 128 * 128 * 4
        span = 64 * 2048
        assert (fp.prefill_vmem_bytes(span, 2048, 64, 4, 128, 1,
                                      "int8")
                < fp.prefill_vmem_bytes(span, 2048, 64, 4, 128, 4))


class TestEnginePrefillPallas:
    def test_engine_chunked_prefill_rides_kernel(self, rng):
        """Engine under pallas="interpret": multi-chunk prompts with
        prefix hits replay bitwise the XLA engine — the chunk kernel,
        span-write kernel, decode kernel and fused sampler compose
        end-to-end, compile discipline intact."""
        prefix = rng.randint(0, 40, 16).astype(np.int32)
        prompts = [
            np.concatenate([prefix,
                            rng.randint(0, 40, 5).astype(np.int32)]),
            np.concatenate([prefix,
                            rng.randint(0, 40, 7).astype(np.int32)]),
            rng.randint(0, 40, 3).astype(np.int32)]
        outs, hits = {}, {}
        for mode in ("interpret", "off"):
            eng = PagedDecodeEngine.from_params(
                PARAMS, CFG, batch=2, cache_len=48, block_size=BS,
                chunk_tokens=8, seed=0, tracker=CompileTracker(),
                pallas=mode)
            reqs = []
            for p in prompts:               # sequential: later prompts
                reqs.append(eng.submit(p, max_new=5))   # hit the cache
                eng.run_until_idle()
            outs[mode] = [r.output.tolist() for r in reqs]
            hits[mode] = [r.prefix_hit_tokens for r in reqs]
            assert eng.compile_counts()["decode"] == 1
        assert outs["interpret"] == outs["off"]
        assert hits["interpret"] == hits["off"]
        assert hits["off"][1] == 16         # the hit path was exercised
