"""Speculative decoding: the batched verify program must be BITWISE
the sequential decode steps it replaces, the accept/reject fold must
be distribution-exact, and the spec engine's greedy output must be
bitwise-identical to the target-only engine — acceptance moves
throughput, never tokens."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import transformer
from paddle_tpu.observe.compile_tracker import CompileTracker
from paddle_tpu.serving import (PagedDecodeEngine, SpecDecodeEngine,
                                sampling)

CFG = transformer.TransformerConfig(
    vocab=40, d_model=16, n_heads=2, n_kv_heads=1, n_layers=2, d_ff=32,
    max_len=64, dtype=jnp.float32, use_rope=True)
CFG_ABS = transformer.TransformerConfig(
    vocab=40, d_model=16, n_heads=2, n_layers=2, d_ff=32,
    max_len=64, dtype=jnp.float32, use_rope=False)
PARAMS = transformer.init_params(jax.random.PRNGKey(0), CFG)
DRAFT_CFG = transformer.TransformerConfig(
    vocab=40, d_model=16, n_heads=2, n_kv_heads=1, n_layers=1, d_ff=32,
    max_len=64, dtype=jnp.float32, use_rope=True)
DRAFT_PARAMS = transformer.init_params(jax.random.PRNGKey(7), DRAFT_CFG)

BS = 8


def _pool_state(params, cfg, rng, B=2, Tp=6, T=32):
    """(pool, pages, last, pos) after a prefill — decode-ready state
    (head-major pool [L, Hkv, M, Dh])."""
    prompt = jnp.asarray(rng.randint(0, 40, (B, Tp)), jnp.int32)
    logits, cache = transformer.prefill(params, prompt, cfg, T)
    pool = {k: jnp.moveaxis(jnp.reshape(
        v, (cfg.n_layers, B * T, cfg.kv_heads, cfg.head_dim)), 1, 2)
        for k, v in cache.items()}
    pages = jnp.asarray(np.arange(B * (T // BS), dtype=np.int32)
                        .reshape(B, T // BS))
    return (pool, pages, jnp.argmax(logits, -1).astype(jnp.int32),
            jnp.full((B,), Tp, jnp.int32))


class TestVerifyStepPaged:
    @pytest.mark.parametrize("cfg", [CFG, CFG_ABS],
                             ids=["rope", "learned-pos"])
    def test_verify_bitwise_matches_sequential_decode(self, cfg, rng):
        """One W-token verify window == W sequential decode steps,
        bitwise, logits AND written pool — the property that lets the
        spec engine promise bitwise-greedy output."""
        params = transformer.init_params(jax.random.PRNGKey(0), cfg)
        pool, pages, tok, pos = _pool_state(params, cfg, rng)
        B, W = tok.shape[0], 4
        active = jnp.ones((B,), bool)
        seq_logits, window = [], [tok]
        ps, toks, p = pool, tok, pos
        for j in range(W):
            lg, ps = transformer.decode_step_paged(
                params, ps, toks, p, active, pages, cfg, block_size=BS)
            seq_logits.append(np.asarray(lg))
            toks = jnp.argmax(lg, -1).astype(jnp.int32)
            if j < W - 1:
                window.append(toks)
            p = p + 1
        vlg, pool_v = transformer.verify_step_paged(
            params, pool, jnp.stack(window, axis=1), pos,
            jnp.full((B,), W, jnp.int32), active, pages, cfg,
            block_size=BS)
        for j in range(W):
            np.testing.assert_array_equal(seq_logits[j],
                                          np.asarray(vlg)[:, j])
        for leaf in pool:
            np.testing.assert_array_equal(np.asarray(ps[leaf]),
                                          np.asarray(pool_v[leaf]))

    def test_invalid_rows_and_inactive_slots_write_nothing(self, rng):
        """Rows >= valid and inactive slots drop their pool writes —
        the decode isolation contract extended to windows."""
        pool, pages, tok, pos = _pool_state(PARAMS, CFG, rng)
        B, W = tok.shape[0], 4
        window = jnp.tile(tok[:, None], (1, W))
        active = jnp.asarray([True, False])
        valid = jnp.asarray([2, 4], jnp.int32)
        _, pool_v = transformer.verify_step_paged(
            PARAMS, pool, window, pos, valid, active, pages, CFG,
            block_size=BS)
        k0, k1 = np.asarray(pool["k"]), np.asarray(pool_v["k"])
        # slot 0 wrote exactly rows pos..pos+1 of its own span (the
        # head-major pool's position axis is axis 2)
        Tp = int(pos[0])
        changed = np.flatnonzero(
            np.abs(k1 - k0).sum(axis=(0, 1, 3)))
        assert set(changed) <= {Tp, Tp + 1}, changed
        # slot 1 (inactive): its physical rows 32..63 untouched
        np.testing.assert_array_equal(k0[:, :, 32:], k1[:, :, 32:])

    def test_verify_int8_pool_matches_xla_decode(self, rng):
        """Quantized pools ride the verify window with write-time
        quantization — bitwise the sequential quantized decode.
        (B >= 2: a one-row decode lowers as a matvec whose accumulation
        differs from the window's gemm at the ulp level — the engine
        contract, like the bench, is the gemm regime.)"""
        pool = transformer.init_block_pool(CFG, 8, BS, kv_dtype="int8")
        B, W = 2, 3
        pages = jnp.asarray(np.arange(8, dtype=np.int32)
                            .reshape(2, 4))
        toks = []
        for b in range(B):
            prompt = rng.randint(0, 40, 5).astype(np.int32)
            padded = np.zeros((1, 8), np.int32)
            padded[0, :5] = prompt
            lg, pool = transformer.prefill_into_blocks(
                PARAMS, pool, jnp.asarray(padded),
                jnp.asarray(5, jnp.int32), pages[b, :1], CFG,
                block_size=BS)
            toks.append(int(jnp.argmax(lg, -1)[0]))
        tok = jnp.asarray(toks, jnp.int32)
        pos = jnp.asarray([5, 5], jnp.int32)
        active = jnp.ones((B,), bool)
        ps, toks, p = pool, tok, pos
        seq, window = [], [tok]
        for j in range(W):
            lg2, ps = transformer.decode_step_paged(
                PARAMS, ps, toks, p, active, pages, CFG, block_size=BS)
            seq.append(np.asarray(lg2))
            toks = jnp.argmax(lg2, -1).astype(jnp.int32)
            if j < W - 1:
                window.append(toks)
            p = p + 1
        vlg, pool_v = transformer.verify_step_paged(
            PARAMS, pool, jnp.stack(window, axis=1), pos,
            jnp.full((B,), W, jnp.int32), active, pages, CFG,
            block_size=BS)
        for j in range(W):
            np.testing.assert_array_equal(seq[j], np.asarray(vlg)[:, j])
        for leaf in pool:
            np.testing.assert_array_equal(np.asarray(ps[leaf]),
                                          np.asarray(pool_v[leaf]))


class TestSpecAccept:
    def test_leading_match_run_plus_correction(self):
        X = jnp.asarray([[5, 6, 7, 8], [5, 6, 7, 8], [1, 2, 3, 4],
                         [5, 6, 7, 8]])
        D = jnp.asarray([[5, 6, 7], [5, 9, 7], [9, 9, 9], [5, 6, 7]])
        valid = jnp.asarray([4, 4, 4, 2])
        n = sampling.spec_accept(X, D, valid)
        # full run -> k+1; break at j=1 -> 2; no match -> 1;
        # valid caps the run regardless of matches
        np.testing.assert_array_equal(np.asarray(n), [4, 2, 1, 2])

    def test_w1_window_is_plain_decode(self):
        n = sampling.spec_accept(jnp.asarray([[3]]),
                                 jnp.zeros((1, 0), jnp.int32),
                                 jnp.asarray([1]))
        assert int(n[0]) == 1

    def test_greedy_rows_bitwise_sample_tokens(self, rng):
        """The verify sampler's greedy rows are exactly the decode
        epilogue's argmax — same function, same axis length."""
        logits = jnp.asarray(rng.randn(2, 3, 40), jnp.float32)
        draft = jnp.zeros((2, 2), jnp.int32)
        X, _ = sampling.spec_verify_tokens(
            logits, draft, jax.random.PRNGKey(0),
            jnp.zeros((2,), jnp.float32), jnp.zeros((2,), jnp.int32),
            jnp.asarray([3, 3], jnp.int32))
        want = sampling.sample_tokens(
            logits.reshape(6, 40), jax.random.PRNGKey(0),
            jnp.zeros((6,), jnp.float32), jnp.zeros((6,), jnp.int32))
        np.testing.assert_array_equal(np.asarray(X).reshape(-1),
                                      np.asarray(want))

    def test_fused_spec_verify_interpret_matches_xla(self, rng):
        """The Pallas accept/reject epilogue (interpret mode) emits the
        same greedy tokens and counts as spec_verify_tokens."""
        from paddle_tpu.ops.pallas import decode as pallas_decode
        logits = jnp.asarray(rng.randn(2, 3, 40), jnp.float32)
        tgt = jnp.argmax(logits, -1)
        # perfect draft: proposal j+1 equals the target's own token at
        # window row j (draft = window[1:] is matched against X[:-1])
        draft = tgt[:, :-1].astype(jnp.int32)
        valid = jnp.asarray([3, 3], jnp.int32)
        temp = jnp.zeros((2,), jnp.float32)
        topk = jnp.zeros((2,), jnp.int32)
        Xf, nf = pallas_decode.fused_spec_verify(
            logits, draft, jnp.asarray(0, jnp.int32), temp, topk,
            valid, interpret=True)
        Xs, ns = sampling.spec_verify_tokens(
            logits, draft, jax.random.PRNGKey(0), temp, topk, valid)
        np.testing.assert_array_equal(np.asarray(Xf), np.asarray(Xs))
        np.testing.assert_array_equal(np.asarray(nf), np.asarray(ns))
        assert list(np.asarray(nf)) == [3, 3]


def _mk_paged(**kw):
    args = dict(batch=3, cache_len=32, block_size=BS, chunk_tokens=8,
                num_blocks=12, seed=0)
    args.update(kw)
    return PagedDecodeEngine.from_params(
        PARAMS, CFG, tracker=CompileTracker(), **args)


def _mk_spec(draft_params=DRAFT_PARAMS, draft_cfg=DRAFT_CFG, k=3, **kw):
    args = dict(batch=3, cache_len=32, block_size=BS, chunk_tokens=8,
                num_blocks=12, seed=0)
    args.update(kw)
    return SpecDecodeEngine.from_params(
        PARAMS, CFG, draft_params, draft_cfg, spec_k=k, **args)


class TestSpecEngine:
    def test_precompile_covers_the_spec_program_set(self, rng):
        """precompile() runs the chunk grid of BOTH pools plus propose /
        draft_verify / verify on inert inputs: traffic afterwards
        compiles nothing new and decodes what a lazy engine decodes."""
        prompts = [rng.randint(0, 40, n).astype(np.int32) for n in (5, 13)]

        def run(eng):
            reqs = [eng.submit(p, max_new=10) for p in prompts]
            eng.run_until_idle()
            return [list(r.tokens) for r in reqs]

        lazy, warm = _mk_spec(), _mk_spec()
        counts = warm.precompile()
        spans = warm.cache_len // warm.chunk_tokens
        assert counts == {
            "prefill": spans * len(warm.buckets),
            "draft_prefill": spans * len(warm.buckets), "decode": 0,
            "propose": 1, "verify": 1, "draft_verify": 1}
        assert run(warm) == run(lazy)
        assert warm.compile_counts() == counts

    def test_greedy_bitwise_vs_target_only(self, rng):
        """Full traces through both engines: outputs identical even
        with an unrelated draft (acceptance is low, tokens equal)."""
        prompts = [rng.randint(0, 40, n).astype(np.int32)
                   for n in (5, 9, 13, 3, 17)]

        def run(eng):
            reqs = [eng.submit(p, max_new=12) for p in prompts]
            eng.run_until_idle()
            return [list(r.tokens) for r in reqs]

        ref = run(_mk_paged())
        eng = _mk_spec()
        assert run(eng) == ref
        acc = eng.acceptance_rate()
        assert acc is not None and 0.0 <= acc < 1.0
        assert eng.pool.idle

    def test_identical_draft_acceptance_is_one(self, rng):
        """Draft == target: every greedy proposal matches the target's
        argmax, so acceptance is exactly 1.0."""
        prompts = [rng.randint(0, 40, n).astype(np.int32)
                   for n in (5, 9)]
        eng = _mk_spec(draft_params=PARAMS, draft_cfg=CFG)
        for p in prompts:
            eng.submit(p, max_new=10)
        eng.run_until_idle()
        assert eng.acceptance_rate() == 1.0

    def test_eos_mid_window_stops_emission(self, rng):
        """An accepted window containing eos finishes the request at
        the eos token; later window tokens are discarded."""
        prompt = rng.randint(0, 40, 5).astype(np.int32)
        ref_eng = _mk_paged(batch=1)
        # pick an eos id that actually occurs a few tokens in
        r0 = ref_eng.submit(prompt, max_new=12)
        ref_eng.run_until_idle()
        eos = r0.tokens[4]
        ref_eng2 = _mk_paged(batch=1)
        ra = ref_eng2.submit(prompt, max_new=12, eos_id=int(eos))
        ref_eng2.run_until_idle()
        eng = _mk_spec(draft_params=PARAMS, draft_cfg=CFG, batch=1)
        rb = eng.submit(prompt, max_new=12, eos_id=int(eos))
        eng.run_until_idle()
        assert list(rb.tokens) == list(ra.tokens)
        assert rb.finish_reason == ra.finish_reason == "eos"

    def test_compile_discipline_draft_adds_target_unchanged(self, rng):
        """The spec engine compiles the draft's own program set plus
        one propose + one verify; the TARGET chunk-program set matches
        the plain paged engine's and plain decode never compiles."""
        prompts = [rng.randint(0, 40, n).astype(np.int32)
                   for n in (5, 13)]
        ref = _mk_paged()
        for p in prompts:
            ref.submit(p, max_new=8)
        ref.run_until_idle()
        eng = _mk_spec()
        for p in prompts:
            eng.submit(p, max_new=8)
        eng.run_until_idle()
        c, rc = eng.compile_counts(), ref.compile_counts()
        assert c["prefill"] == rc["prefill"]
        assert c["draft_prefill"] == rc["prefill"]
        assert c["propose"] == 1 and c["verify"] == 1
        assert c["decode"] == 0 and rc["decode"] == 1

    def test_spec_preempt_resume_bitwise(self, rng):
        """Preemption + both resume paths compose with spec decode:
        the victim's output stays bitwise the unpreempted spec run's
        (which is itself bitwise the target-only run's)."""
        prompt = rng.randint(0, 40, 8).astype(np.int32)
        solo = _mk_spec(batch=2, num_blocks=4)
        r = solo.submit(prompt, max_new=16)
        solo.run_until_idle()
        ref = list(r.tokens)
        for adv_len, adv_new, mode in ((8, 4, "remap"),
                                       (16, 16, "replay")):
            eng = _mk_spec(batch=2, num_blocks=4)
            v = eng.submit(prompt, max_new=16, tier="batch")
            for _ in range(4):
                eng.step()
            assert v.status == "running"
            eng.submit(rng.randint(0, 40, adv_len).astype(np.int32),
                       max_new=adv_new, tier="latency")
            eng.step()
            assert v.status == "preempted"
            eng.run_until_idle()
            assert list(v.tokens) == ref, mode
            assert int(eng.metrics.get("engine_resumes_total").value(
                mode=mode)) == 1, mode
            assert eng.pool.idle

    def test_propose_masks_writes_beyond_valid(self, rng):
        """Near end-of-generation (valid < k+1) the propose scan's
        later steps would write through the ZEROED page-table tail into
        physical block 0 of the draft pool — another slot's rows. The
        valid mask must drop those writes."""
        fns = sampling.paged_spec_fns(CFG, DRAFT_CFG, BS, 3,
                                      pallas="off")
        pool = transformer.init_block_pool(DRAFT_CFG, 6, BS)
        # sentinel bytes in physical block 0 (some other slot's rows;
        # the head-major position axis is axis 2)
        pool = {k: v.at[:, :, :BS].set(7.0) for k, v in pool.items()}
        pages = jnp.asarray([[3, 0, 0]], jnp.int32)   # 1 allocated page
        pos = jnp.asarray([BS - 1], jnp.int32)        # last row of it
        _, out = fns["propose"](
            DRAFT_PARAMS, pool, jnp.asarray([1], jnp.int32), pos,
            jnp.asarray([True]), jnp.asarray([1], jnp.int32), pages)
        for leaf in ("k", "v"):
            np.testing.assert_array_equal(
                np.asarray(out[leaf])[:, :, :BS], 7.0)  # block 0 intact
        # ...while the one VALID step's write landed in block 3
        row = 3 * BS + BS - 1
        assert np.abs(np.asarray(out["k"])[:, :, row]).sum() > 0

    def test_health_reports_spec_section(self, rng):
        eng = _mk_spec()
        eng.submit(rng.randint(0, 40, 5).astype(np.int32), max_new=6)
        eng.run_until_idle()
        doc = eng.health()
        assert doc["spec"]["k"] == 3
        assert doc["spec"]["rounds"] >= 1
        assert doc["spec"]["acceptance_rate"] is not None

    def test_draft_vocab_mismatch_rejected(self):
        bad = transformer.TransformerConfig(
            vocab=39, d_model=16, n_heads=2, n_kv_heads=1, n_layers=1,
            d_ff=32, max_len=64, dtype=jnp.float32, use_rope=True)
        with pytest.raises(ValueError, match="vocab"):
            SpecDecodeEngine.from_params(
                PARAMS, CFG,
                transformer.init_params(jax.random.PRNGKey(1), bad),
                bad, spec_k=2, batch=2, cache_len=32, block_size=BS,
                chunk_tokens=8, seed=0)


class TestSpecArtifactV5:
    def test_v5_roundtrip_bitwise(self, rng, tmp_path):
        """save -> load -> SpecDecodeEngine: the artifact engine's
        greedy output is bitwise the in-process spec engine's."""
        from paddle_tpu.io import lm_serving
        path = str(tmp_path / "m.tar")
        lm_serving.save_lm_artifact(
            path, PARAMS, CFG, batch=3, prompt_len=8, cache_len=32,
            engine_buckets=(8,), engine_block_size=BS,
            engine_draft_params=DRAFT_PARAMS,
            engine_draft_config=DRAFT_CFG, engine_spec_k=3)
        srv = lm_serving.load_lm_artifact(path)
        assert srv.meta["format_version"] == 5
        eng = srv.engine()
        assert isinstance(eng, SpecDecodeEngine)
        prompts = [rng.randint(0, 40, n).astype(np.int32)
                   for n in (5, 9)]
        reqs = [eng.submit(p, max_new=8) for p in prompts]
        eng.run_until_idle()
        ref_eng = _mk_spec()
        ref = [ref_eng.submit(p, max_new=8) for p in prompts]
        ref_eng.run_until_idle()
        assert [list(r.tokens) for r in reqs] == \
            [list(r.tokens) for r in ref]

    def test_draft_needs_paged_export(self):
        import tempfile

        from paddle_tpu.io import lm_serving
        with tempfile.TemporaryDirectory() as d:
            with pytest.raises(
                    ValueError,
                    match="engine_draft_params needs engine_buckets"):
                lm_serving.save_lm_artifact(
                    f"{d}/m.tar", PARAMS, CFG, batch=2, prompt_len=8,
                    cache_len=32,
                    engine_draft_params=DRAFT_PARAMS,
                    engine_draft_config=DRAFT_CFG)
