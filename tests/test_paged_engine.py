"""Paged KV engine: block-table decode must be bitwise-faithful to the
lockstep decode step, chunked prefill must reproduce monolithic
prefill, the
block pool must never leak or double-free, prefix-cache hits must serve
bitwise the cold-prefill tokens, and the engine still compiles once per
chunk bucket + once for decode."""

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import transformer
from paddle_tpu.observe.compile_tracker import CompileTracker
from paddle_tpu.serving import BlockPool, PagedDecodeEngine

CFG = transformer.TransformerConfig(
    vocab=40, d_model=16, n_heads=2, n_kv_heads=1, n_layers=2, d_ff=32,
    max_len=64, dtype=jnp.float32, use_rope=True)
CFG_ABS = transformer.TransformerConfig(
    vocab=40, d_model=16, n_heads=2, n_layers=2, d_ff=32,
    max_len=64, dtype=jnp.float32, use_rope=False)
PARAMS = transformer.init_params(jax.random.PRNGKey(0), CFG)

BS = 8          # block size shared by the kernel contracts below


def _pool_from_arena(cache, cfg):
    """Lockstep cache [L, B, T, Hkv, Dh] -> head-major flat pool
    [L, Hkv, M, Dh] with the identity paging (slot b's pages tile its contiguous
    span)."""
    L, B, T = cache["k"].shape[:3]
    pool = {k: jnp.moveaxis(jnp.reshape(
        v, (L, B * T, cfg.kv_heads, cfg.head_dim)), 1, 2)
        for k, v in cache.items()}
    pages = np.arange(B * (T // BS), dtype=np.int32).reshape(B, T // BS)
    return pool, jnp.asarray(pages)


def _paged(batch=2, cache_len=32, block_size=8, chunk_tokens=8,
           num_blocks=None, seed=0, params=PARAMS, cfg=CFG):
    return PagedDecodeEngine.from_params(
        params, cfg, batch=batch, cache_len=cache_len,
        block_size=block_size, chunk_tokens=chunk_tokens,
        num_blocks=num_blocks, seed=seed, tracker=CompileTracker())


class TestPagedKernels:
    @pytest.mark.parametrize("cfg", [CFG, CFG_ABS],
                             ids=["rope", "learned-pos"])
    def test_paged_decode_bitwise_matches_slots(self, cfg, rng):
        """Identity paging, one shared position: the active slots of
        decode_step_paged == the lockstep decode_step, bitwise on the
        CPU (logits AND written cache; the gathered view has the
        lockstep cache's shape, so the reductions are the same), both
        position encodings; the inactive slot's rows keep their
        bytes."""
        params = transformer.init_params(jax.random.PRNGKey(0), cfg)
        B, Tp, T = 3, 6, 32
        prompt = jnp.asarray(rng.randint(0, 40, (B, Tp)), jnp.int32)
        logits, cache = transformer.prefill(params, prompt, cfg, T)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        l_lock, c_lock = transformer.decode_step(
            params, cache, tok, jnp.asarray(Tp, jnp.int32), cfg)
        on = [0, 2]
        active = jnp.asarray([True, False, True])
        pool, pages = _pool_from_arena(cache, cfg)
        l_paged, c_paged = transformer.decode_step_paged(
            params, pool, tok, jnp.full((B,), Tp, jnp.int32), active,
            pages, cfg, block_size=BS)
        np.testing.assert_array_equal(np.asarray(l_lock)[on],
                                      np.asarray(l_paged)[on])
        for leaf in ("k", "v"):
            a = np.asarray(c_lock[leaf]).copy()     # [L, B, T, Hkv, Dh]
            a[:, 1] = np.asarray(cache[leaf])[:, 1]     # never written
            want = np.moveaxis(a.reshape(
                (a.shape[0], -1) + a.shape[3:]), 1, 2)
            np.testing.assert_array_equal(want, np.asarray(c_paged[leaf]))

    def test_scrambled_pages_same_logits(self, rng):
        """Physical block placement is invisible: a permuted page table
        holding the same logical content decodes bitwise identically."""
        B, Tp, T = 2, 6, 32
        P = T // BS
        prompt = jnp.asarray(rng.randint(0, 40, (B, Tp)), jnp.int32)
        logits, cache = transformer.prefill(PARAMS, prompt, CFG, T)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        pos = jnp.full((B,), Tp, jnp.int32)
        active = jnp.ones((B,), bool)
        pool, pages = _pool_from_arena(cache, CFG)
        l_id, _ = transformer.decode_step_paged(
            PARAMS, pool, tok, pos, active, pages, CFG, block_size=BS)
        # scramble: permute the physical blocks, remap the page table
        perm = rng.permutation(B * P).astype(np.int32)
        scat = np.empty_like(perm)
        scat[perm] = np.arange(B * P, dtype=np.int32)
        gidx = (perm[:, None] * BS + np.arange(BS)).reshape(-1)
        pool2 = {k: jnp.asarray(np.asarray(v)[:, :, gidx])
                 for k, v in pool.items()}
        pages2 = jnp.asarray(scat[np.asarray(pages).reshape(-1)]
                             .reshape(B, P))
        l_sc, _ = transformer.decode_step_paged(
            PARAMS, pool2, tok, pos, active, pages2, CFG, block_size=BS)
        np.testing.assert_array_equal(np.asarray(l_id), np.asarray(l_sc))

    def test_chunked_prefill_matches_single_chunk(self, rng):
        """Chunked prefill on the fixed (block-aligned) chunk grid
        reproduces one monolithic prefill within tolerance — the chunk
        program attends over concat(context, chunk), a different einsum
        shape than the monolithic pass — and the SAME chunk grid
        replayed onto a different physical block placement is BITWISE
        identical (the kernel core of the prefix-cache hit-replay
        guarantee)."""
        Tp = 14
        prompt = rng.randint(0, 40, Tp).astype(np.int32)

        def run(chunks, pages):
            pool, off, lg = transformer.init_block_pool(CFG, 6, BS), 0, \
                None
            for c in chunks:
                bucket = 8 if c <= 8 else 16
                padded = np.zeros((1, bucket), np.int32)
                padded[0, :c] = prompt[off:off + c]
                pv = pages[:off // BS + -(-bucket // BS)]
                lg, pool = transformer.prefill_into_blocks(
                    PARAMS, pool, jnp.asarray(padded),
                    jnp.asarray(c, jnp.int32),
                    jnp.asarray(pv, jnp.int32), CFG, block_size=BS)
                off += c
            return lg, pool

        lg1, pool1 = run([14], np.asarray([0, 1], np.int32))
        lg2, pool2 = run([8, 6], np.asarray([0, 1], np.int32))
        np.testing.assert_allclose(np.asarray(lg1), np.asarray(lg2),
                                   rtol=1e-5, atol=1e-6)
        for leaf in ("k", "v"):
            np.testing.assert_allclose(np.asarray(pool1[leaf]),
                                       np.asarray(pool2[leaf]),
                                       rtol=1e-5, atol=1e-6)
        # same grid, scrambled physical placement: bitwise
        lg3, pool3 = run([8, 6], np.asarray([4, 2], np.int32))
        np.testing.assert_array_equal(np.asarray(lg2), np.asarray(lg3))
        for leaf in ("k", "v"):
            a = np.asarray(pool2[leaf])
            b = np.asarray(pool3[leaf])
            np.testing.assert_array_equal(a[:, :, 0 * BS:1 * BS],
                                          b[:, :, 4 * BS:5 * BS])
            np.testing.assert_array_equal(a[:, :, 1 * BS:2 * BS],
                                          b[:, :, 2 * BS:3 * BS])

    def test_transposed_scatter_touches_only_the_mapped_row(self, rng):
        """Sentinel-pool isolation of the head-major decode scatter:
        a decode step writes EXACTLY one pool row per active slot (its
        page-mapped position) — every other row of every block keeps
        its sentinel bytes bitwise, on the XLA path AND the interpret
        kernel. A transpose bug that scattered on the wrong axis (or
        broadcast across heads) could silently corrupt another slot's
        blocks while that slot's own logits still looked fine; the
        sentinel pins it."""
        M = 6 * BS
        sentinel = {
            "k": jnp.full((CFG.n_layers, CFG.kv_heads, M,
                           CFG.head_dim), 11.5, jnp.float32),
            "v": jnp.full((CFG.n_layers, CFG.kv_heads, M,
                           CFG.head_dim), -4.75, jnp.float32)}
        tok = jnp.asarray([7, 3], jnp.int32)
        pos = jnp.asarray([9, 4], jnp.int32)       # -> page 1 off 1 / drop
        active = jnp.asarray([True, False])
        pages = jnp.asarray([[5, 2], [0, 3]], jnp.int32)
        w = 2 * BS + 9 % BS                        # slot 0's write row
        for mode in ("off", "interpret"):
            _, out = transformer.decode_step_paged(
                PARAMS, dict(sentinel), tok, pos, active, pages, CFG,
                block_size=BS, pallas=mode)
            for leaf, want in (("k", 11.5), ("v", -4.75)):
                got = np.asarray(out[leaf])
                mask = np.ones(M, bool)
                mask[w] = False
                # every row except the single mapped write: sentinel
                np.testing.assert_array_equal(got[:, :, mask], want)
                # the mapped row changed in every layer and head
                assert (got[:, :, w] != want).any(axis=-1).all()

    def test_prefill_into_blocks_matches_slot_prefill(self, rng):
        """Block prefill of a right-padded chunk reproduces the
        lockstep prefill's last-position logits and its KV rows, and
        leaves unmapped blocks zero. Tolerance, not bitwise: the chunk
        program attends over concat(context, chunk) at the bucket's
        length, the lockstep pass over the unpadded prompt — programs
        of different shapes round differently (ROADMAP Queue 3, the
        bitwise contract)."""
        Tp, T = 6, 24
        prompt = jnp.asarray(rng.randint(0, 40, (1, Tp)), jnp.int32)
        lg_lock, c_lock = transformer.prefill(PARAMS, prompt, CFG, T)
        padded = jnp.pad(prompt, ((0, 0), (0, 2)))          # bucket 8
        pool = transformer.init_block_pool(CFG, 6, BS)
        pages = jnp.asarray([3], jnp.int32)     # one scrambled page:
        lg, pool = transformer.prefill_into_blocks(  # ctx 0, bucket 8
            PARAMS, pool, padded, jnp.asarray(Tp, jnp.int32), pages,
            CFG, block_size=BS)
        np.testing.assert_allclose(np.asarray(lg_lock), np.asarray(lg),
                                   rtol=1e-5, atol=1e-6)
        for leaf in ("k", "v"):
            np.testing.assert_allclose(
                np.moveaxis(np.asarray(c_lock[leaf])[:, 0, :Tp], 1, 2),
                np.asarray(pool[leaf])[:, :, 3 * BS:3 * BS + Tp],
                rtol=1e-5, atol=1e-6)
        k = np.asarray(pool["k"])
        for b in (0, 1, 2, 4, 5):                            # unmapped
            np.testing.assert_array_equal(
                k[:, :, b * BS:(b + 1) * BS], 0.0)


class TestBlockPool:
    def test_reserve_alloc_release_accounting(self):
        pool = BlockPool(4, 8)
        assert pool.allocatable == 4 and pool.idle
        pool.reserve(3)
        assert not pool.can_reserve(2) and pool.can_reserve(1)
        a, b = pool.alloc(), pool.alloc()
        assert pool.in_use == 2 and pool.reserved == 1
        pool.unreserve(1)
        pool.release(a)
        pool.release(b)
        assert pool.idle and pool.free_count == 4
        with pytest.raises(RuntimeError, match="reservation"):
            pool.alloc()

    def test_refcounted_sharing_and_lru_park(self):
        pool = BlockPool(2, 4)
        pool.reserve(1)
        b = pool.alloc()
        pool.publish(b"h1", b)
        pool.share(b)                        # second holder
        pool.release(b)                      # first gone
        assert pool.refcount(b) == 1 and pool.in_use == 1
        pool.release(b)                      # last gone -> LRU, not free
        assert pool.cached_free_count == 1 and pool.free_count == 1
        assert pool.lookup(b"h1") == b       # still serves hits
        pool.share(b)                        # revival out of the LRU
        assert pool.refcount(b) == 1 and pool.cached_free_count == 0
        pool.release(b)

    def test_lru_eviction_oldest_first_unpublishes(self):
        pool = BlockPool(2, 4)
        pool.reserve(2)
        b1, b2 = pool.alloc(), pool.alloc()
        pool.publish(b"h1", b1)
        pool.publish(b"h2", b2)
        pool.release(b1)                     # LRU order: b1 oldest
        pool.release(b2)
        pool.reserve(1)
        got = pool.alloc()                   # evicts b1, not b2
        assert got == b1 and pool.evictions == 1
        assert pool.lookup(b"h1") is None and pool.lookup(b"h2") == b2
        pool.release(got)

    def test_double_release_and_share_free_guards(self):
        pool = BlockPool(2, 4)
        pool.reserve(1)
        b = pool.alloc()
        pool.release(b)
        with pytest.raises(RuntimeError, match="refcount"):
            pool.release(b)
        with pytest.raises(RuntimeError, match="not cached"):
            pool.share(b)


class TestPagedEngineScheduling:
    def test_matches_generate_mixed_lengths(self, rng):
        """Greedy paged-engine output == transformer.generate per
        request, mixed prompt lengths sharing the pool."""
        eng = _paged()
        prompts = [rng.randint(0, 40, n).astype(np.int32)
                   for n in (5, 9, 3)]
        reqs = [eng.submit(p, max_new=6) for p in prompts]
        done = eng.run_until_idle()
        assert len(done) == 3
        for r, p in zip(reqs, prompts):
            want = np.asarray(transformer.generate(
                PARAMS, jnp.asarray(p[None]), CFG, max_new=6))[0]
            np.testing.assert_array_equal(r.output, want)
            assert r.finish_reason == "max_tokens"

    def test_chunk_buckets_are_the_one_bucket_list(self, rng):
        """``chunk_buckets`` is the engine's only bucket list: entries
        beyond ``cache_len`` have no program and are dropped, a list
        with none left is refused at construction, and ``/healthz``
        reports what is left under ``prefill_buckets``."""
        def make(buckets):
            return PagedDecodeEngine.from_params(
                PARAMS, CFG, batch=2, cache_len=32, block_size=8,
                chunk_tokens=8, chunk_buckets=buckets, seed=0,
                tracker=CompileTracker(), decode_flops=None)

        eng = make((8, 4, 64))
        assert eng.buckets == (4, 8)
        assert eng.health()["prefill_buckets"] == [4, 8]
        r = eng.submit(rng.randint(0, 40, 11).astype(np.int32),
                       max_new=3)       # chunks of 8 and 3 -> bucket 4
        eng.run_until_idle()
        assert r.finish_reason == "max_tokens"
        assert eng.compile_counts() == {"prefill": 2, "decode": 1}
        with pytest.raises(ValueError, match="no chunk bucket fits"):
            make((64,))

    def test_long_prompt_chunked_no_bucket_rejection(self, rng):
        """A prompt far beyond chunk_tokens is admitted (no bucket
        bounds a prompt) and decodes correctly through chunked
        prefill."""
        eng = _paged(cache_len=32, chunk_tokens=8)
        p = rng.randint(0, 40, 26).astype(np.int32)
        r = eng.submit(p, max_new=6)         # 26 > chunk max 8
        short = eng.submit(rng.randint(0, 40, 4).astype(np.int32),
                           max_new=4)
        eng.run_until_idle()
        want = np.asarray(transformer.generate(
            PARAMS, jnp.asarray(p[None]), CFG, max_new=6))[0]
        np.testing.assert_array_equal(r.output, want)
        assert short.finish_reason == "max_tokens"
        with pytest.raises(ValueError, match="exceed cache_len"):
            eng.submit(rng.randint(0, 40, 28).astype(np.int32),
                       max_new=8)

    def test_prefix_hit_bitwise_identical_to_cold(self, rng):
        """Prefix-cache-hit generation is bitwise the cold prefill's:
        same prompt replayed, and a shared-prefix different-tail prompt
        vs its own cold engine."""
        prefix = rng.randint(0, 40, 16).astype(np.int32)
        tail_a = rng.randint(0, 40, 5).astype(np.int32)
        tail_b = rng.randint(0, 40, 7).astype(np.int32)
        pa = np.concatenate([prefix, tail_a])
        pb = np.concatenate([prefix, tail_b])

        cold = _paged(cache_len=48, chunk_tokens=8)
        ra_cold = cold.submit(pa, max_new=6)
        cold.run_until_idle()
        rb_cold = cold.submit(pb, max_new=6)
        cold.run_until_idle()
        assert ra_cold.prefix_hit_tokens == 0
        assert rb_cold.prefix_hit_tokens == 16      # pa cached the prefix

        warm = _paged(cache_len=48, chunk_tokens=8)
        warm.submit(pa, max_new=6)
        warm.run_until_idle()
        ra_hit = warm.submit(pa, max_new=6)         # full-prompt replay
        warm.run_until_idle()
        assert ra_hit.prefix_hit_tokens == 16
        assert ra_hit.tokens == ra_cold.tokens
        # different tail over the shared prefix, vs ITS cold run
        rb_hit = warm.submit(pb, max_new=6)
        warm.run_until_idle()
        assert rb_hit.prefix_hit_tokens == 16
        assert rb_hit.tokens == rb_cold.tokens

    def test_shared_blocks_survive_one_requesters_finish(self, rng):
        """Refcounting: two in-flight requests share prefix blocks; the
        first one's termination must not free or corrupt them for the
        second."""
        prefix = rng.randint(0, 40, 16).astype(np.int32)
        pa = np.concatenate([prefix, rng.randint(0, 40, 3).astype(np.int32)])
        pb = np.concatenate([prefix, rng.randint(0, 40, 5).astype(np.int32)])
        solo = _paged(cache_len=48, chunk_tokens=8)
        rb_solo = solo.submit(pb, max_new=10)
        solo.run_until_idle()

        eng = _paged(cache_len=48, chunk_tokens=8)
        eng.submit(pa, max_new=2)
        eng.run_until_idle()                  # publishes the prefix
        ra = eng.submit(pa, max_new=2)        # hits, finishes early
        rb = eng.submit(pb, max_new=10)       # hits, decodes long
        eng.run_until_idle()
        assert ra.prefix_hit_tokens == 16 and rb.prefix_hit_tokens == 16
        assert ra.finish_reason == "max_tokens"
        np.testing.assert_array_equal(rb.output, rb_solo.output)

    def test_no_block_leak_after_full_trace(self, rng):
        """After a drained trace every block is back (free or parked in
        the LRU), nothing reserved, and the in-use gauge reads 0."""
        eng = _paged(batch=2, cache_len=32, chunk_tokens=8)
        total = eng.pool.num_blocks
        alloc0 = eng.pool.free_count + eng.pool.cached_free_count
        for n in (5, 20, 9, 3, 26, 13, 7):
            eng.submit(rng.randint(0, 40, n).astype(np.int32),
                       max_new=int(rng.randint(1, 6)))
        eng.run_until_idle()
        assert eng.pool.idle
        # published blocks PARK in the LRU rather than returning to
        # free, so the no-leak invariant is on the ALLOCATABLE count
        assert eng.pool.free_count + eng.pool.cached_free_count \
            == alloc0 == total
        assert eng.metrics.get("engine_blocks_in_use").value() == 0
        assert eng.metrics.get("engine_blocks_free").value() == \
            eng.pool.free_count

    def test_lru_eviction_under_pressure_keeps_correctness(self, rng):
        """A pool sized for ~1 request forces LRU eviction of cached
        prefix blocks; results stay exact and the eviction counter
        moves."""
        eng = _paged(batch=1, cache_len=32, chunk_tokens=8,
                     num_blocks=4)
        prompts = [rng.randint(0, 40, 17).astype(np.int32)
                   for _ in range(3)]
        for p in prompts:
            r = eng.submit(p, max_new=4)
            eng.run_until_idle()
            want = np.asarray(transformer.generate(
                PARAMS, jnp.asarray(p[None]), CFG, max_new=4))[0]
            np.testing.assert_array_equal(r.output, want)
        assert eng.pool.evictions > 0
        assert eng.metrics.get(
            "engine_prefix_cache_evictions_total").value() == \
            eng.pool.evictions

    def test_compile_once_per_chunk_shape_plus_decode(self, rng):
        """Each distinct (chunk bucket, context span) pair compiles
        exactly once; every decode step shares ONE compilation
        regardless of paging."""
        from paddle_tpu.core import ragged
        eng = _paged(batch=2, cache_len=32, chunk_tokens=8)
        lens = (3, 26, 9, 12)
        for n in lens:
            eng.submit(rng.randint(0, 40, n).astype(np.int32),
                       max_new=4)
        eng.run_until_idle()
        progs = set()       # the chunk walk the scheduler performs
        for n in lens:
            off = 0
            while off < n:
                c = min(n - off, eng.chunk_tokens)
                b = ragged.bucket_length(c, eng.buckets)
                progs.add((b, off // eng.block_size
                           + -(-b // eng.block_size)))
                off += c
        counts = eng.compile_counts()
        assert counts["decode"] == 1
        assert counts["prefill"] == len(progs) == 4

    def test_admission_waits_for_blocks(self, rng):
        """A request that cannot reserve its worst case waits FIFO even
        with a free slot; it admits once blocks release."""
        eng = _paged(batch=2, cache_len=32, chunk_tokens=8,
                     num_blocks=4)
        big_a = eng.submit(rng.randint(0, 40, 17).astype(np.int32),
                           max_new=7)          # 3 blocks
        big_b = eng.submit(rng.randint(0, 40, 17).astype(np.int32),
                           max_new=7)          # needs 3 more: waits
        eng.step()
        assert big_a.status != "queued" and big_b.status == "queued"
        eng.run_until_idle()
        assert big_b.finish_reason == "max_tokens"
        want = np.asarray(transformer.generate(
            PARAMS, jnp.asarray(big_b.prompt[None]), CFG, max_new=7))[0]
        np.testing.assert_array_equal(big_b.output, want)

    def test_submit_rejects_worst_case_beyond_pool(self, rng):
        """A request whose worst-case block need exceeds the POOL (not
        just cache_len) must be rejected at submit: it could never
        reserve, and would livelock the FIFO queue head forever."""
        eng = _paged(batch=2, cache_len=32, chunk_tokens=8,
                     num_blocks=3)             # pool < cache_len/bs
        with pytest.raises(ValueError, match="blocks"):
            eng.submit(rng.randint(0, 40, 17).astype(np.int32),
                       max_new=8)              # needs ceil(25/8) = 4
        # the worst case that fits the pool is still served
        ok = eng.submit(rng.randint(0, 40, 17).astype(np.int32),
                        max_new=7)             # needs exactly 3
        eng.run_until_idle()
        assert ok.finish_reason == "max_tokens"

    def test_metrics_and_health(self, rng):
        eng = _paged(cache_len=32, chunk_tokens=8)
        prefix = rng.randint(0, 40, 8).astype(np.int32)
        for tail in (3, 5):     # sequential: the second prompt's prefix
            eng.submit(np.concatenate(  # block hits the first's cache
                [prefix, rng.randint(0, 40, tail).astype(np.int32)]),
                max_new=4)
            eng.run_until_idle()
        assert eng.metrics.get(
            "engine_prefix_cache_hit_blocks_total").value() >= 1
        assert eng.metrics.get(
            "engine_prefix_cache_miss_blocks_total").value() >= 1
        assert eng.metrics.get("engine_prefill_chunks_total").value() >= 2
        text = eng.metrics_text()
        assert "# TYPE engine_prefill_stall_seconds histogram" in text
        assert "engine_blocks_in_use" in text
        h = eng.health()
        assert h["blocks_total"] == eng.pool.num_blocks
        assert h["blocks_in_use"] == 0 and h["block_size"] == 8


def _reference_decode_step_paged(params, cache, tokens, pos, active,
                                 pages, cfg, *, block_size):
    """The decode step as it was spelt before the pool went in place,
    kept here as the plain reference: the pool rides the layer scan as
    ``xs``/``ys`` (each layer's slab sliced out, written by a scatter
    on its position axis, read by a gather on the same axis, stacked
    back), XLA attention, dense FFN."""
    import math

    from paddle_tpu.ops import q8 as ops_q8
    T = transformer
    B, P = pages.shape
    bs = int(block_size)
    Tl = P * bs
    H, Dh, Hkv = cfg.n_heads, cfg.head_dim, cfg.kv_heads
    kvd = Hkv * Dh
    M = cache["k"].shape[2]
    kvq = T.pool_kv_dtype(cache, cfg)
    x = T._embed_rows(params, tokens, cfg)
    if not cfg.use_rope:
        x = x + jnp.take(params["pos"], pos, axis=0).astype(cfg.dtype)
    rope_tabs = T._rope_tables(pos, Dh, cfg.rope_theta) \
        if cfg.use_rope else None
    gidx = (pages[:, :, None] * bs
            + jnp.arange(bs, dtype=jnp.int32)[None, None, :]
            ).reshape(B, Tl)
    wpage = jnp.take_along_axis(pages, (pos // bs)[:, None],
                                axis=1)[:, 0]
    widx = jnp.where(active, wpage * bs + pos % bs, M)
    attend = jnp.arange(Tl, dtype=jnp.int32)[None, :] <= pos[:, None]
    names = tuple(n for n in ("k", "v", "k_scale", "v_scale")
                  if n in cache)

    def block(x, scanned):
        w, tabs = scanned[0], dict(zip(names, scanned[1:]))
        h = T._layer_norm(x, w["ln1"], w["ln1_b"])
        qkv = h @ w["qkv"].astype(h.dtype)
        q, k, v = jnp.split(qkv, [H * Dh, H * Dh + kvd], axis=-1)
        if cfg.use_rope:
            q = T._rope_rows(q.reshape(B, H, Dh), rope_tabs).reshape(
                B, H * Dh)
            k = T._rope_rows(k.reshape(B, Hkv, Dh), rope_tabs).reshape(
                B, kvd)
        new = {"k": k.reshape(B, Hkv, Dh), "v": v.reshape(B, Hkv, Dh)}
        if kvq != "none":
            new["k"], new["k_scale"] = ops_q8.quantize_kv(new["k"], kvq)
            new["v"], new["v_scale"] = ops_q8.quantize_kv(new["v"], kvq)
        tabs = {n: t.at[:, widx].set(
            jnp.swapaxes(new[n], 0, 1).astype(t.dtype), mode="drop")
            for n, t in tabs.items()}
        seen = {n: jnp.transpose(
            jnp.take(t, gidx, axis=1),
            (1, 2, 0) + tuple(range(3, t.ndim + 1)))
            for n, t in tabs.items()}            # [B, T, Hkv, ...]
        if kvq != "none":
            kt = ops_q8.dequantize_kv(seen["k"], seen["k_scale"], kvq)
            vt = ops_q8.dequantize_kv(seen["v"], seen["v_scale"], kvq)
        else:
            kt = seen["k"].astype(jnp.float32)
            vt = seen["v"].astype(jnp.float32)
        q32 = q.reshape(B, Hkv, H // Hkv, Dh).astype(jnp.float32)
        s = jnp.einsum("bkgd,btkd->bkgt", q32, kt) / math.sqrt(Dh)
        s = jnp.where(attend[:, None, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        attn = jnp.einsum("bkgt,btkd->bkgd", p, vt)
        attn = attn.reshape(B, cfg.d_model).astype(cfg.dtype)
        x = x + attn @ w["attn_out"].astype(attn.dtype)
        h2 = T._layer_norm(x, w["ln2"], w["ln2_b"])
        ff = jax.nn.gelu(h2 @ w["mlp_in"].astype(h2.dtype))
        x = x + ff @ w["mlp_out"].astype(ff.dtype)
        return x, tuple(tabs[n] for n in names)

    x, out = jax.lax.scan(
        block, x, (params["blocks"],) + tuple(cache[n] for n in names))
    x = T._layer_norm(x, params["ln_f"], params["ln_f_b"])
    return T._vocab_logits(x, params), dict(zip(names, out))


CFG_BF16 = transformer.TransformerConfig(
    vocab=40, d_model=32, n_heads=4, n_kv_heads=2, n_layers=3, d_ff=64,
    max_len=64, dtype=jnp.bfloat16, use_rope=False)


def _random_pool(cfg, num_blocks, kv_dtype, rng):
    """A pool whose every row holds something: what a step must leave
    alone shows if it does not."""
    pool = transformer.init_block_pool(cfg, num_blocks, BS,
                                       kv_dtype=kv_dtype)
    out = {}
    for n, t in pool.items():
        if t.dtype == jnp.int8:
            a = rng.randint(-127, 128, t.shape)
        elif n.endswith("_scale"):
            a = rng.uniform(0.001, 0.02, t.shape)
        else:
            a = rng.standard_normal(t.shape)
        out[n] = jnp.asarray(a, t.dtype)
    return out


class TestPoolInPlace:
    """The decode program carries the pool through its layer loop and
    is called with the pool donated: same values as the xs/ys spelling,
    and no caller is left holding a donated pool."""

    @pytest.mark.parametrize("paging", ["identity", "shuffled"])
    @pytest.mark.parametrize("kv_dtype", [None, "int8"],
                             ids=["bf16", "int8"])
    def test_in_place_decode_bitwise_matches_xs_ys_reference(
            self, kv_dtype, paging, rng):
        cfg = CFG_BF16
        params = transformer.init_params(jax.random.PRNGKey(1), cfg)
        B, P = 3, 4
        pool = _random_pool(cfg, B * P, kv_dtype, rng)
        ids = np.arange(B * P, dtype=np.int32)
        if paging == "shuffled":
            ids = rng.permutation(ids).astype(np.int32)
        pages = jnp.asarray(ids.reshape(B, P))
        tok = jnp.asarray(rng.randint(0, 40, B), jnp.int32)
        pos = jnp.asarray([9, 4, 17], jnp.int32)
        active = jnp.asarray([True, False, True])
        want_l, want_pool = jax.jit(
            lambda p, c: _reference_decode_step_paged(
                p, c, tok, pos, active, pages, cfg, block_size=BS))(
            params, pool)
        donated = {n: jnp.copy(t) for n, t in pool.items()}
        got_l, got_pool = jax.jit(
            lambda p, c: transformer.decode_step_paged(
                p, c, tok, pos, active, pages, cfg, block_size=BS,
                pallas="off"), donate_argnums=(1,))(params, donated)
        assert all(t.is_deleted() for t in donated.values())
        np.testing.assert_array_equal(np.asarray(want_l),
                                      np.asarray(got_l))
        assert sorted(got_pool) == sorted(pool)
        for n in pool:
            assert got_pool[n].shape == pool[n].shape
            assert got_pool[n].dtype == pool[n].dtype
            np.testing.assert_array_equal(np.asarray(want_pool[n]),
                                          np.asarray(got_pool[n]))
        # the inactive row wrote nothing; the active rows wrote theirs
        before, after = np.asarray(pool["k"]), np.asarray(got_pool["k"])
        changed = np.flatnonzero((before != after).any(axis=(0, 1, 3)))
        rows = [int(ids[b * P + p // BS]) * BS + p % BS
                for b, p in ((0, 9), (2, 17))]
        assert sorted(changed) == sorted(rows)

    @pytest.mark.parametrize("resume", ["remap", "replay"])
    @pytest.mark.parametrize("kv_dtype", [None, "int8"],
                             ids=["fp32", "int8"])
    def test_engine_never_touches_a_donated_pool(self, kv_dtype, resume,
                                                 rng):
        """Over a preempt/resume schedule every program call consumes
        the pool it is handed: the engine's pool is live after every
        step, the one it held before is gone, and the victim's output
        is the unpreempted run's (a read of a donated pool raises)."""
        def engine():
            return PagedDecodeEngine.from_params(
                PARAMS, CFG, batch=2, cache_len=32, block_size=BS,
                chunk_tokens=8, num_blocks=4, seed=0,
                kv_dtype=kv_dtype, tracker=CompileTracker())

        prompt = rng.randint(0, 40, 8).astype(np.int32)
        solo = engine()
        ref = solo.submit(prompt, max_new=16)
        solo.run_until_idle()
        eng = engine()
        eng.precompile()
        v = eng.submit(prompt, max_new=16, tier="batch")
        consumed = 0

        def step():
            nonlocal consumed
            held = dict(eng.cache)
            eng.step()
            assert not any(t.is_deleted() for t in eng.cache.values())
            if eng.cache["k"] is not held["k"]:
                consumed += 1
                assert all(t.is_deleted() for t in held.values())

        for _ in range(6):
            step()
        # remap: the victim's parked blocks survive; replay: the
        # adversary's worst case is the whole pool and evicts them
        n = 8 if resume == "remap" else 16
        lat = eng.submit(rng.randint(0, 40, n).astype(np.int32),
                         max_new=n, tier="latency")
        step()
        assert v.status == "preempted"
        while not eng.idle:
            step()
        assert consumed >= 16
        assert lat.finish_reason == "max_tokens"
        assert list(v.tokens) == list(ref.tokens)
        assert int(eng.metrics.get("engine_resumes_total").value(
            mode=resume)) == 1


# -- the block matrices in the compute dtype (PR 29) -------------------------

def _step_programs(cfg, B=3, P=4, C=8):
    """{name: (fn of (params, pool), its pool)}: the paged step
    programs at fixed small inputs."""
    rng = np.random.RandomState(5)
    pool = _random_pool(cfg, B * P, None, rng)
    pages = jnp.asarray(np.arange(B * P, dtype=np.int32).reshape(B, P))
    tok = jnp.asarray(rng.randint(0, 40, B), jnp.int32)
    pos = jnp.asarray([9, 4, 17], jnp.int32)
    active = jnp.asarray([True, False, True])
    chunk = jnp.asarray(rng.randint(0, 40, (1, C)), jnp.int32)
    window = jnp.asarray(rng.randint(0, 40, (B, 3)), jnp.int32)
    valid = jnp.asarray([3, 1, 2], jnp.int32)
    return {
        "decode": lambda p, c: transformer.decode_step_paged(
            p, c, tok, pos, active, pages, cfg, block_size=BS,
            pallas="off"),
        # the second chunk of a prompt: context pages + the chunk's own
        "prefill": lambda p, c: transformer.prefill_into_blocks(
            p, c, chunk, jnp.int32(C - 2), pages[1, :2], cfg,
            block_size=BS, pallas="off"),
        "verify": lambda p, c: transformer.verify_step_paged(
            p, c, window, pos, valid, active, pages, cfg,
            block_size=BS)}, pool


def _matrix_converts(jaxpr, shapes):
    """``convert_element_type`` equations, anywhere in ``jaxpr``, whose
    operand has one of ``shapes``."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "convert_element_type" \
                and eqn.invars[0].aval.shape in shapes:
            n += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _matrix_converts(sub, shapes)
    return n


class TestMatricesInComputeDtype:
    """``transformer.compute_dtype_params``: a serving program takes
    the four block matrices in ``cfg.dtype``, so the block's
    ``w[...].astype(h.dtype)`` is a no-op in the program."""

    @pytest.mark.parametrize("tree", ["helper", "float32"])
    @pytest.mark.parametrize("program", ["decode", "prefill", "verify"])
    def test_no_cast_of_a_block_matrix_in_the_program(self, program,
                                                      tree):
        """The test that fails if the cast creeps back: over the
        helper's tree no ``convert_element_type`` has an operand of a
        block matrix's shape (one layer's, or the stack's, were it
        hoisted); over raw float32 leaves the layer body holds four."""
        cfg = CFG_BF16
        params = transformer.init_params(jax.random.PRNGKey(1), cfg)
        shapes = set()
        for n in transformer.COMPUTE_DTYPE_LEAVES:
            shapes |= {params["blocks"][n].shape,
                       params["blocks"][n].shape[1:]}
        if tree == "helper":
            params = transformer.compute_dtype_params(params, cfg)
        programs, pool = _step_programs(cfg)
        jaxpr = jax.make_jaxpr(programs[program])(params, pool).jaxpr
        assert _matrix_converts(jaxpr, shapes) \
            == (0 if tree == "helper" else 4)

    @pytest.mark.parametrize("program", ["decode", "prefill", "verify"])
    def test_bitwise_the_float32_tree(self, program):
        """A bf16 value cast from the same float32 value once is the
        value the step cast every time: logits and pool bit-equal."""
        cfg = CFG_BF16
        params = transformer.init_params(jax.random.PRNGKey(1), cfg)
        cast = transformer.compute_dtype_params(params, cfg)
        assert cast["blocks"]["qkv"].dtype == jnp.bfloat16
        assert cast["embed"] is params["embed"]
        assert cast["blocks"]["ln1"] is params["blocks"]["ln1"]
        programs, pool = _step_programs(cfg)
        fn = jax.jit(programs[program])
        want, got = fn(params, pool), fn(cast, pool)
        for a, b in zip(jax.tree_util.tree_leaves(want),
                        jax.tree_util.tree_leaves(got)):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("cfg", [CFG, CFG_BF16], ids=["fp32", "bf16"])
    def test_from_params_casts_once_and_counts_the_bytes(self, cfg, rng):
        """The in-process engine is handed the helper's tree (under a
        float32 config: the tree it was given), ``engine_weight_bytes``
        reads that tree's bytes, and the served ids are those of an
        engine handed the cast tree."""
        params = transformer.init_params(jax.random.PRNGKey(1), cfg)
        want = transformer.compute_dtype_params(params, cfg)
        assert (want is params) == (cfg.dtype == jnp.float32)

        def engine(p):
            return PagedDecodeEngine.from_params(
                p, cfg, batch=2, cache_len=32, block_size=BS,
                chunk_tokens=8, seed=0, tracker=CompileTracker())

        eng, eng_cast = engine(params), engine(want)
        assert eng_cast.params is want
        for a, b in zip(jax.tree_util.tree_leaves(eng.params),
                        jax.tree_util.tree_leaves(want)):
            assert a.dtype == b.dtype
        nbytes = sum(int(x.nbytes)
                     for x in jax.tree_util.tree_leaves(want))
        assert eng.metrics.get("engine_weight_bytes").value() == nbytes
        prompts = [rng.randint(0, 40, n).astype(np.int32) for n in (5, 12)]
        out = []
        for e in (eng, eng_cast):
            reqs = [e.submit(p, max_new=6) for p in prompts]
            e.run_until_idle()
            out.append([list(r.tokens) for r in reqs])
        assert out[0] == out[1]


# -- one decode step in flight -----------------------------------------------

SKELETONS = ("gpt2", "gated_hybrid", "latent_moe")


@functools.lru_cache(maxsize=None)
def _skeleton(name):
    """(cfg, params, engine keywords) of a tiny model of one skeleton;
    the two skeletons run their XLA path."""
    if name == "gpt2":
        return CFG, PARAMS, dict(batch=3, cache_len=32, block_size=BS,
                                 chunk_tokens=8)
    common = dict(vocab=64, d_model=32, n_heads=2, d_ff=32, max_len=128,
                  dtype=jnp.float32, use_rope=True, rope_theta=1e4,
                  skeleton=name, moe_experts=8, moe_top_k=2,
                  moe_shared_ff=32)
    if name == "gated_hybrid":
        from paddle_tpu.models import gated_hybrid as module
        cfg = transformer.TransformerConfig(
            n_kv_heads=1, n_layers=4, attn_head_dim=16, rotary_dim=8,
            full_attn_interval=4, rec_key_heads=2, rec_value_heads=2,
            rec_key_dim=16, rec_value_dim=16, rec_conv=4, moe_held=(0, 8),
            **common)
    else:
        from paddle_tpu.models import latent_moe as module
        cfg = transformer.TransformerConfig(
            n_layers=2, norm_eps=1e-5, q_lora_rank=16, kv_lora_rank=32,
            qk_nope_dim=8, qk_rope_dim=8, v_head_dim=8, dense_layers=1,
            dense_ff=48, moe_route_scale=1.8, **common)
    return cfg, module.init_params(jax.random.PRNGKey(0), cfg), dict(
        batch=3, cache_len=64, block_size=16, chunk_tokens=32,
        chunk_buckets=(32,), pallas="off")


def _lookahead_engine(name, **kw):
    cfg, params, ekw = _skeleton(name)
    return PagedDecodeEngine.from_params(
        params, cfg, seed=0, tracker=CompileTracker(), decode_flops=0.0,
        **dict(ekw, **kw))


def _prompts(name, lens, seed):
    r = np.random.RandomState(seed)
    return [r.randint(0, _skeleton(name)[0].vocab, n).astype(np.int32)
            for n in lens]


def _one_at_a_time(eng, prompts, max_new):
    """Each request alone in the batch, in turn: its tokens."""
    out = []
    for p, m in zip(prompts, max_new):
        r = eng.submit(p, m)
        eng.run_until_idle()
        out.append(list(r.tokens))
    return out


def _eos_inside(tokens):
    """(index, id) of the first token after the first that did not
    occur before it: an EOS id a request meets in mid-decode."""
    return next((k, t) for k, t in enumerate(tokens)
                if k >= 1 and t not in tokens[:k])


def _overlapped(eng):
    return eng.metrics.get("engine_decode_overlapped_total").value()


@pytest.mark.parametrize("name", SKELETONS)
def test_lookahead_mixed_batch_greedy_bitwise(name):
    """Five requests on three slots with a decode step always in flight:
    each request's tokens are bitwise those it gets alone in the batch,
    the one that ends on an EOS id (taken from its solo run) included,
    and the others ending on ``max_new``."""
    prompts = _prompts(name, (5, 9, 3, 12, 7), seed=11)
    max_new = [10, 7, 12, 6, 9]
    alone = _one_at_a_time(_lookahead_engine(name), prompts, max_new)
    if name == "gpt2":          # the solo runs against the lockstep model
        for p, m, got in zip(prompts, max_new, alone):
            want = np.asarray(transformer.generate(
                PARAMS, jnp.asarray(p[None]), CFG, max_new=m))[0]
            assert got == list(want[p.size:])
    k, eos = _eos_inside(alone[0])
    eng = _lookahead_engine(name)
    reqs = [eng.submit(p, m, eos_id=eos if i == 0 else None)
            for i, (p, m) in enumerate(zip(prompts, max_new))]
    assert len(eng.run_until_idle()) == 5
    assert reqs[0].finish_reason == "eos"
    assert list(reqs[0].tokens) == alone[0][:k + 1]
    for r, want in zip(reqs[1:], alone[1:]):
        assert r.finish_reason == "max_tokens"
        assert list(r.tokens) == want
    assert _overlapped(eng) > 0
    assert eng.pool.idle


@pytest.mark.parametrize("name", SKELETONS)
def test_lookahead_slot_and_pages_reused_after_eos(name):
    """A request that ends on EOS while the step after it is already in
    flight hands its slot and its pages to a successor: that step's row
    of the finished request is discarded at read-back, and the
    successor's tokens are bitwise those it gets first in an engine of
    its own."""
    bs = _skeleton(name)[2]["block_size"]
    short = name == "gpt2"
    pa, pb, pc = _prompts(name, (9, 5, 6) if short else (20, 10, 12),
                          seed=11)
    ma, mb, mc = (8, 20, 8) if short else (12, 30, 8)

    def need(p, m):
        return -(-(p.size + m) // bs)

    # the pool holds the first two; the third can only have the first's
    assert need(pc, mc) <= need(pa, ma)
    ref = _lookahead_engine(name, batch=2)
    want_c, want_a = _one_at_a_time(ref, (pc, pa), (mc, ma))
    k, eos = _eos_inside(want_a)
    assert k < ma - 1
    eng = _lookahead_engine(name, batch=2,
                            num_blocks=need(pa, ma) + need(pb, mb))
    discarded = []
    land = eng._land

    def noting(rec):
        discarded.extend(req for _, req, emits in rec.rows
                         if emits and req.status == "done")
        return land(rec)

    eng._land = noting
    a = eng.submit(pa, ma, eos_id=eos)
    b = eng.submit(pb, mb)
    c = eng.submit(pc, mc)
    eng.run_until_idle()
    assert a.finish_reason == "eos" and list(a.tokens) == want_a[:k + 1]
    assert discarded == [a]
    assert c.slot == a.slot and len(b.tokens) == mb
    assert list(c.tokens) == want_c
    assert eng.pool.idle


@pytest.mark.parametrize("name", SKELETONS)
def test_lookahead_preempt_with_a_step_in_flight_resumes_bitwise(name):
    """``_preempt`` reads the step in flight back first: the victim's
    cursor is exact and it resumes (by re-mapping its pages, or by
    replay where the model keeps recurrent rows) to the tokens of an
    undisturbed run."""
    pv, pd = _prompts(name, (9, 4), seed=7)
    want, = _one_at_a_time(_lookahead_engine(name), (pv,), (12,))
    eng = _lookahead_engine(name)
    d = eng.submit(pd, 20)
    v = eng.submit(pv, 12)
    while not (v.status == "running" and len(v.tokens) >= 2):
        eng.step()
    assert eng._inflight is not None
    assert v in [req for _, req, _ in eng._inflight.rows]
    eng._preempt(v.slot)
    assert eng._inflight is None and v.status == "preempted"
    eng.run_until_idle()
    assert list(v.tokens) == want and len(d.tokens) == 20
    assert eng.pool.idle


def test_lookahead_last_step_read_back_before_admission():
    """When the step in flight is the last (its one row reached
    ``max_new`` at dispatch), the next ``step()`` reads it back before
    admission: the request it finishes is returned, the request queued
    behind it gets the slot in that same call, and the time until that
    request's first decode step is not a step's period."""
    eng = _lookahead_engine("gpt2", batch=1)
    pa, pb = _prompts("gpt2", (5, 6), seed=3)
    a = eng.submit(pa, 3)
    b = eng.submit(pb, 3)
    eng.step()
    while eng._active.any():
        eng.step()
    assert eng._inflight is not None and a.status == "running"
    assert b.status == "queued"
    done = eng.step()
    assert done == [a] and a.finish_reason == "max_tokens"
    assert b.status == "running" and b.slot == 0
    assert eng._inflight is not None and not eng._inflight.overlapped
    assert eng._step_end is None
    eng.run_until_idle()
    assert len(b.tokens) == 3 and eng.pool.idle


def test_lookahead_read_back_inside_another_phase_counts_once():
    """A read-back made inside another phase (``_preempt`` under
    ``schedule``, as admission calls it) lands in ``decode_sync`` and
    ``emit``, and ``schedule`` leaves those seconds out: the phases'
    series add up to no more than the wall time they cover."""
    eng = _lookahead_engine("gpt2")
    pv, pd = _prompts("gpt2", (9, 4), seed=7)
    eng.submit(pd, 20)
    v = eng.submit(pv, 12)
    while not (v.status == "running" and eng._inflight is not None):
        eng.step()
    names = ("schedule", "decode_sync", "emit")

    def seconds():
        return {n: eng.metrics.get(f"engine_{n}_seconds").snapshot()["sum"]
                for n in names}

    before = seconds()
    t0 = time.perf_counter()
    with eng.phase("schedule"):
        eng._preempt(v.slot)
    wall = time.perf_counter() - t0
    got = {n: s - before[n] for n, s in seconds().items()}
    assert eng._inflight is None and v.status == "preempted"
    assert got["decode_sync"] > 0 and got["emit"] > 0
    assert sum(got.values()) <= wall
    assert got["schedule"] < wall - got["decode_sync"] - got["emit"] \
        + 1e-9


@pytest.mark.parametrize("name", ["gpt2", "latent_moe"])
def test_lookahead_export_prefix_with_a_step_in_flight(name):
    """``export_prefix`` reads the step in flight back first and returns
    the payload it returns once the engine has drained; what the read-
    back finished, the next step returns."""
    chunk = _skeleton(name)[2]["chunk_tokens"]
    prompt, other = _prompts(name, (chunk + 4, 5), seed=9)
    eng = _lookahead_engine(name)
    a = eng.submit(prompt, 6)
    b = eng.submit(other, 2)
    while not (a.status == "running" and eng._inflight is not None):
        eng.step()
    early = eng.export_prefix(prompt)
    assert early is not None and eng._inflight is None
    done = eng.run_until_idle()
    assert {r.rid for r in done} | {r.rid for r in (a, b)
                                    if r.status == "done"} \
        == {a.rid, b.rid}
    assert a.status == b.status == "done"
    assert eng.export_prefix(prompt) == early


@pytest.mark.parametrize("name", SKELETONS)
def test_lookahead_abort_with_a_step_in_flight_leaks_no_block(name):
    """``abort_requests`` reads the step in flight back, aborts what is
    still live and gives every slot's blocks back."""
    eng = _lookahead_engine(name)
    reqs = [eng.submit(p, 10) for p in _prompts(name, (5, 9, 3, 7),
                                                seed=13)]
    for _ in range(3):
        eng.step()
    assert eng._inflight is not None
    aborted = eng.abort_requests("replica_killed")
    assert aborted == sum(r.status == "aborted" for r in reqs) >= 3
    assert all(r.status in ("aborted", "done") for r in reqs)
    assert eng._inflight is None and eng.pool.idle
    assert eng.free_slots == eng.batch and not eng._active.any()


def test_lookahead_staged_arguments_are_the_host_state_at_dispatch(rng):
    """The decode program's vectors and page table are the host's as
    they were at staging: moving the host on (as ``_advance`` does while
    the step is queued) leaves them as they were."""
    eng = _lookahead_engine("gpt2")
    for n in (5, 9):
        eng.submit(rng.randint(0, 40, n).astype(np.int32), max_new=6)
    eng.step()
    eng.step()
    assert eng._inflight is not None
    eng._pages_dev = None
    host = [eng._pos, eng._active, eng._temp, eng._topk, eng._pages]
    want = [h.copy() for h in host]
    args, _ = eng._stage_decode(np.int32(0), eng._inflight.ids)
    for h in host:
        h[...] = 1 - h
    got = [args[3], args[4], args[6], args[7], args[5]]
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), w)


def test_lookahead_chunk_own_time_excludes_the_step_ahead(rng):
    """A chunk dispatched behind a decode step still in flight waits for
    it: that wait is the prompt's stall, not its own prefill time (a
    slow decode program here: every step burns device time before its
    ids and pool are ready)."""
    eng = _lookahead_engine("gpt2")
    decode = eng._decode_fn

    @jax.jit
    def burn(ids, pool):
        x = jnp.ones((256, 256), jnp.float32) / 256.0
        x = jax.lax.fori_loop(0, 400, lambda _, v: jnp.tanh(v @ v), x)
        z = (x[0, 0] * 0.0).astype(ids.dtype)
        return ids + z, jax.tree_util.tree_map(
            lambda t: t + z.astype(t.dtype), pool)

    ids, pool = burn(jnp.zeros(3, jnp.int32), eng.cache)
    t0 = time.perf_counter()
    np.asarray(burn(ids, pool)[0])
    burn_s = time.perf_counter() - t0

    def slow(*args):
        return burn(*decode(*args))

    eng._decode_fn = slow
    eng.submit(rng.randint(0, 40, 4).astype(np.int32), max_new=12)
    eng.step()
    eng.step()
    assert eng._inflight is not None
    victim = eng.submit(rng.randint(0, 40, 5).astype(np.int32), max_new=1)
    eng.run_until_idle()
    assert victim.finish_reason == "max_tokens"
    assert victim.prefill_own_s < burn_s / 3 < victim.prefill_stall_s


@pytest.mark.parametrize("name", SKELETONS)
def test_lookahead_counters_and_compiles(name):
    """Every decode step dispatched with the step before still unread
    counts in ``engine_decode_overlapped_total``; the engine is not idle
    while a step is in flight; traffic compiles nothing beyond what
    ``precompile()`` compiled, the input select included."""
    eng = _lookahead_engine(name)
    counts = eng.precompile()
    selects = eng._next_inputs._cache_size()
    fresh = []
    advance = eng._advance

    def noting(ids, phases, prev):
        fresh.append(prev is None)
        return advance(ids, phases, prev)

    eng._advance = noting
    prompts = _prompts(name, (5, 9, 3, 12, 7), seed=17)
    eng.submit(prompts[0], 3)
    eng.step()                  # its first decode step dispatched, unread
    assert eng._inflight is not None and not eng.idle
    assert eng._active.sum() == 1
    for p in prompts[1:]:
        eng.submit(p, 8)
    eng.run_until_idle()
    assert eng.idle and eng._inflight is None
    steps = eng.metrics.get("engine_decode_steps_total").value()
    assert steps == len(fresh)
    assert _overlapped(eng) == steps - sum(fresh) > 0
    assert eng.compile_counts() == counts
    assert eng._next_inputs._cache_size() == selects
