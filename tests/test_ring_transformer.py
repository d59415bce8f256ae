"""Ring attention (context parallelism) + transformer LM.

Distributed tests run on the 8-device virtual CPU mesh (SURVEY.md §4.6
strategy — the in-process pserver analog).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.core import place
from paddle_tpu.models import transformer
from paddle_tpu.parallel import ring


class TestRingAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_full_attention(self, rng, causal):
        mesh = place.make_mesh((2, 4), (place.AXIS_DATA, place.AXIS_SEQ))
        B, T, H, D = 4, 16, 2, 8
        q = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
        k = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
        v = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
        lens = jnp.asarray(np.array([16, 9, 12, 5], np.int32))
        got = ring.ring_attention_spmd(q, k, v, mesh, causal=causal,
                                       lengths=lens)
        want = ring.full_attention(q, k, v, causal=causal, lengths=lens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_grads_match_full_attention(self, rng):
        mesh = place.make_mesh((1, 8), (place.AXIS_DATA, place.AXIS_SEQ))
        B, T, H, D = 2, 16, 2, 4
        q = rng.randn(B, T, H, D).astype(np.float32)
        k = rng.randn(B, T, H, D).astype(np.float32)
        v = rng.randn(B, T, H, D).astype(np.float32)

        def loss_ring(q_, k_, v_):
            return jnp.sum(ring.ring_attention_spmd(
                jnp.asarray(q_), jnp.asarray(k_), jnp.asarray(v_), mesh,
                causal=True) ** 2)

        def loss_full(q_, k_, v_):
            return jnp.sum(ring.full_attention(
                jnp.asarray(q_), jnp.asarray(k_), jnp.asarray(v_),
                causal=True) ** 2)

        g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        g_full = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ring, g_full):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=5e-5)

    def test_inside_jit(self, rng):
        mesh = place.make_mesh((2, 4), (place.AXIS_DATA, place.AXIS_SEQ))
        B, T, H, D = 2, 8, 1, 4
        q = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))

        @jax.jit
        def f(q_):
            return ring.ring_attention_spmd(q_, q_, q_, mesh, causal=True)

        out = f(q)
        want = ring.full_attention(q, q, q, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


CFG = transformer.TransformerConfig(vocab=50, d_model=32, n_heads=4,
                                    n_layers=2, d_ff=64, max_len=32,
                                    dtype=jnp.float32)


class TestTransformer:
    def test_forward_shapes_and_determinism(self, rng):
        params = transformer.init_params(jax.random.PRNGKey(0), CFG)
        toks = jnp.asarray(rng.randint(0, 50, (2, 16)).astype(np.int32))
        a = transformer.forward(params, toks, CFG)
        b = transformer.forward(params, toks, CFG)
        assert a.shape == (2, 16, 50)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_lm_learns(self, rng):
        params = transformer.init_params(jax.random.PRNGKey(0), CFG)
        B, T = 8, 16
        # learnable pattern: token t+1 = (token t + 1) % vocab
        start = rng.randint(0, 50, (B, 1))
        toks = (start + np.arange(T)[None, :]) % 50
        tgt = (toks + 1) % 50
        toks, tgt = jnp.asarray(toks, jnp.int32), jnp.asarray(tgt, jnp.int32)

        step = jax.jit(jax.value_and_grad(
            lambda p: transformer.lm_loss(p, toks, tgt, CFG)))
        vals, hist = params, []
        for _ in range(30):
            l, g = step(vals)
            vals = jax.tree_util.tree_map(lambda p, gr: p - 0.1 * gr, vals, g)
            hist.append(float(l))
        assert hist[-1] < hist[0] * 0.5, (hist[0], hist[-1])

    @pytest.mark.slow
    def test_spmd_dp_sp_tp_matches_single_device(self, rng):
        """The full 3-axis GSPMD train step must reproduce single-device
        numerics — DP over batch, ring-attention CP over seq, TP over
        heads/MLP.

        `slow`: one of the two observed crash sites of the full-sweep
        XLA:CPU `backend_compile` segfault — see the root-cause account
        on test_ring_matches_full_and_kv_grads_grouped below. The
        grad-of-shard_map compile here (line "g_got = ...") is where
        the 2026-08-07 sweep died."""
        cfg = transformer.TransformerConfig(
            vocab=50, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            max_len=32, dtype=jnp.float32, use_ring_attention=True)
        mesh = place.make_mesh(
            (2, 2, 2), (place.AXIS_DATA, place.AXIS_SEQ, place.AXIS_MODEL))
        params = transformer.init_params(jax.random.PRNGKey(1), cfg)
        shardings = transformer.param_shardings(cfg, mesh)
        sharded = jax.tree_util.tree_map(jax.device_put, params, shardings)
        B, T = 4, 16
        toks = jnp.asarray(rng.randint(0, 50, (B, T)).astype(np.int32))
        tgt = jnp.asarray(rng.randint(0, 50, (B, T)).astype(np.int32))
        lens = jnp.asarray(np.array([16, 10, 16, 7], np.int32))

        ref_cfg = dataclasses.replace(cfg, use_ring_attention=False)
        ref = transformer.lm_loss(params, toks, tgt, ref_cfg, lengths=lens)

        @jax.jit
        def dist_loss(p, tk, tg, ln):
            return transformer.lm_loss(p, tk, tg, cfg, mesh=mesh, lengths=ln)

        got = dist_loss(sharded, toks, tgt, lens)
        np.testing.assert_allclose(float(got), float(ref), rtol=2e-5)

        # grads too: the backward collectives must be correct
        g_ref = jax.grad(lambda p: transformer.lm_loss(
            p, toks, tgt, ref_cfg, lengths=lens))(params)
        g_got = jax.jit(jax.grad(lambda p: transformer.lm_loss(
            p, toks, tgt, cfg, mesh=mesh, lengths=lens)))(sharded)
        ref_flat = jax.tree_util.tree_leaves(g_ref)
        got_flat = jax.tree_util.tree_leaves(g_got)
        for a, b in zip(ref_flat, got_flat):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       rtol=1e-3, atol=1e-4)


class TestRingFlashAttention:
    """Ring CP composed with the Pallas flash kernel as the block engine
    (interpret mode on the CPU mesh; the same code path drives the real
    kernel on TPU)."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_full_attention(self, rng, causal):
        mesh = place.make_mesh((2, 4), (place.AXIS_DATA, place.AXIS_SEQ))
        B, T, H, D = 2, 32, 2, 8
        q = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
        k = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
        v = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
        got = ring.ring_attention_spmd(q, k, v, mesh, causal=causal,
                                       use_flash=True, interpret=True)
        want = ring.full_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_full_attention(self, rng, causal):
        mesh = place.make_mesh((1, 4), (place.AXIS_DATA, place.AXIS_SEQ))
        B, T, H, D = 2, 16, 2, 4
        q = rng.randn(B, T, H, D).astype(np.float32)
        k = rng.randn(B, T, H, D).astype(np.float32)
        v = rng.randn(B, T, H, D).astype(np.float32)

        def loss_ring(q_, k_, v_):
            return jnp.sum(ring.ring_attention_spmd(
                jnp.asarray(q_), jnp.asarray(k_), jnp.asarray(v_), mesh,
                causal=causal, use_flash=True, interpret=True) ** 2)

        def loss_full(q_, k_, v_):
            return jnp.sum(ring.full_attention(
                jnp.asarray(q_), jnp.asarray(k_), jnp.asarray(v_),
                causal=causal) ** 2)

        g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        g_full = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", g_ring, g_full):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=5e-5,
                                       err_msg=f"d{name}")

    def test_rejects_ragged_lengths(self, rng):
        mesh = place.make_mesh((1, 4), (place.AXIS_DATA, place.AXIS_SEQ))
        x = jnp.zeros((2, 16, 2, 4), jnp.float32)
        with pytest.raises(ValueError, match="packed equal-length"):
            ring.ring_attention_spmd(
                x, x, x, mesh, use_flash=True,
                lengths=jnp.asarray([16, 9], jnp.int32))

    def test_causal_bwd_outlier_no_nan(self, rng):
        """Gradient NaN regression: queries aligning far more strongly
        with FUTURE-shard keys than any allowed key make p = exp(s − lse)
        overflow if the excluded block is zeroed after the kernel instead
        of masked inside the exponent."""
        mesh = place.make_mesh((1, 4), (place.AXIS_DATA, place.AXIS_SEQ))
        B, T, H, D = 1, 16, 1, 4
        u = np.ones((D,), np.float32)
        q = np.tile(u * 20, (B, T, H, 1)).astype(np.float32)
        k = rng.randn(B, T, H, D).astype(np.float32) * 0.01
        k[:, 12:] = u * 20          # future shard for most queries
        v = rng.randn(B, T, H, D).astype(np.float32)

        def loss(fn):
            def f(q_, k_, v_):
                return jnp.sum(fn(jnp.asarray(q_), jnp.asarray(k_),
                                  jnp.asarray(v_)) ** 2)
            return f

        ring_fn = lambda a, b, c: ring.ring_attention_spmd(
            a, b, c, mesh, causal=True, use_flash=True, interpret=True)
        full_fn = lambda a, b, c: ring.full_attention(a, b, c, causal=True)
        g_ring = jax.grad(loss(ring_fn), argnums=(0, 1, 2))(q, k, v)
        g_full = jax.grad(loss(full_fn), argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", g_ring, g_full):
            assert np.isfinite(np.asarray(a)).all(), f"d{name} has NaN/inf"
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=5e-5,
                                       err_msg=f"d{name}")


class TestGQAEngines:
    """GQA (Hkv < H) handled INSIDE the attention engines: the ring
    collectives must rotate Hkv-head K/V, and gradients w.r.t. k/v must
    come back at Hkv heads (group-summed), matching the explicitly
    repeated MHA formulation numerically."""

    def _qkv(self, rng, B, T, H, Hkv, D):
        q = rng.randn(B, T, H, D).astype(np.float32)
        k = rng.randn(B, T, Hkv, D).astype(np.float32)
        v = rng.randn(B, T, Hkv, D).astype(np.float32)
        return q, k, v

    def _repeat(self, x, g):
        return np.repeat(x, g, axis=2)

    def test_full_attention_grouped_matches_repeat(self, rng):
        B, T, H, Hkv, D = 2, 12, 4, 2, 8
        q, k, v = self._qkv(rng, B, T, H, Hkv, D)
        lens = jnp.asarray([12, 7], jnp.int32)
        got = ring.full_attention(jnp.asarray(q), jnp.asarray(k),
                                  jnp.asarray(v), causal=True, lengths=lens)
        want = ring.full_attention(
            jnp.asarray(q), jnp.asarray(self._repeat(k, 2)),
            jnp.asarray(self._repeat(v, 2)), causal=True, lengths=lens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.slow
    @pytest.mark.parametrize("use_flash", [False, True])
    def test_ring_matches_full_and_kv_grads_grouped(self, rng, use_flash):
        """Ring GQA fwd + grouped dk/dv grads match the head-repeated
        MHA formulation (full_attention with an explicit repeat whose
        adjoint group-sums).

        `slow` — root-cause findings on the full-sweep XLA:CPU
        `backend_compile` segfault (ROADMAP housekeeping flag from
        PR 15, investigated PR 16): when the tier-1 sweep reaches this
        file at ~80% (~750 s, ~700 tests of jitted programs resident),
        the process dies with SIGSEGV *inside* XLA:CPU compilation of
        whichever of this file's big reverse-mode shard_map programs
        compiles first — PR 15 observed it here, the 2026-08-07 sweep
        died earlier in the file at test_spmd_dp_sp_tp_matches_
        single_device (faulthandler: `jax/_src/compiler.py:307
        backend_compile` under `_scan_transpose`, no repo frame below
        jax). It is NOT this test's code and not any single suite's
        state: both parametrizations pass in isolation (~30 s), after
        the full serving/fleet block (160 tests, one process), and
        after the master/distributed/elastic block (121 tests —
        including the six leaked `MasterService._snapshot_loop` /
        `_beat` daemon threads visible in the crash dump; threads
        exonerated). Host memory is not a factor (128 GB free, 1-core
        host, 8 simulated XLA host devices; first seen under jax 0.4.37,
        still there under the installed jax 0.9.0). Everything
        points at process state accumulated over the FULL sweep
        (hundreds of live LLVM-JIT'd executables) tripping a bug in
        XLA:CPU's compiler on these largest-in-repo grad programs —
        environmental, not reachable from repo code. Marked `slow`
        (with the spmd test above, the other observed crash site) so
        the fast tier stops dying at 80% and the ~18% of the suite
        after this file gets coverage; the slow tier and isolation
        runs still execute both."""
        B, T, H, Hkv, D = 2, 16, 4, 2, 4
        q, k, v = self._qkv(rng, B, T, H, Hkv, D)

        def loss_ring(q_, k_, v_):
            return jnp.sum(ring.ring_attention_spmd(
                jnp.asarray(q_), jnp.asarray(k_), jnp.asarray(v_), mesh,
                causal=True, use_flash=use_flash, interpret=True) ** 2)

        def loss_full(q_, k_, v_):
            return jnp.sum(ring.full_attention(
                jnp.asarray(q_), jnp.asarray(self._repeat(k_, 2)),
                jnp.asarray(self._repeat(v_, 2)), causal=True) ** 2)

        got = ring.ring_attention_spmd(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), mesh,
            causal=True, use_flash=use_flash, interpret=True)
        want = ring.full_attention(
            jnp.asarray(q), jnp.asarray(self._repeat(k, 2)),
            jnp.asarray(self._repeat(v, 2)), causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

        g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        # autodiff folds the repeat's adjoint, so g_full's dk/dv are
        # already the group-sum at Hkv heads — directly comparable
        g_full = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
        assert g_ring[1].shape == (B, T, Hkv, D)
        assert g_full[1].shape == (B, T, Hkv, D)
        for name, a, b in zip("qkv", g_ring, g_full):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=5e-5,
                                       err_msg=f"d{name}")


class TestMoETransformer:
    """moe_experts>0: the FFN is an expert-parallel top-k MoE
    (parallel/moe.moe_ffn) with the load-balance aux loss threaded into
    lm_loss; the dense path keeps its exact behavior."""

    MOE_CFG = transformer.TransformerConfig(
        vocab=50, d_model=32, n_heads=4, n_layers=2, d_ff=64, max_len=32,
        dtype=jnp.float32, moe_experts=4, moe_capacity_factor=4.0)

    def test_single_expert_matches_dense(self, rng):
        """E=1 with weights copied from the dense mlp must reproduce the
        dense forward exactly (gate softmax over one expert = 1)."""
        cfg1 = dataclasses.replace(self.MOE_CFG, moe_experts=1,
                                   moe_capacity_factor=64.0)
        dense = transformer.init_params(jax.random.PRNGKey(0), CFG)
        p1 = transformer.init_params(jax.random.PRNGKey(0), cfg1)
        p1["embed"] = dense["embed"]
        p1["pos"] = dense["pos"]
        p1["ln_f"], p1["ln_f_b"] = dense["ln_f"], dense["ln_f_b"]
        for k in ("ln1", "ln1_b", "qkv", "attn_out", "ln2", "ln2_b"):
            p1["blocks"][k] = dense["blocks"][k]
        p1["blocks"]["moe_w_in"] = dense["blocks"]["mlp_in"][:, None]
        p1["blocks"]["moe_w_out"] = dense["blocks"]["mlp_out"][:, None]
        toks = jnp.asarray(rng.randint(0, 50, (2, 16)).astype(np.int32))
        a = transformer.forward(dense, toks, CFG)
        b = transformer.forward(p1, toks, cfg1)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)

    def test_moe_lm_learns_with_aux(self, rng):
        cfg = self.MOE_CFG
        params = transformer.init_params(jax.random.PRNGKey(0), cfg)
        logits, aux = transformer.forward(
            params, jnp.zeros((2, 8), jnp.int32), cfg, return_aux=True)
        assert float(aux) > 0       # balance loss present
        B, T = 8, 16
        start = rng.randint(0, 50, (B, 1))
        toks = (start + np.arange(T)[None, :]) % 50
        tgt = (toks + 1) % 50
        toks = jnp.asarray(toks, jnp.int32)
        tgt = jnp.asarray(tgt, jnp.int32)
        step = jax.jit(jax.value_and_grad(
            lambda p: transformer.lm_loss(p, toks, tgt, cfg)))
        vals, hist = params, []
        for _ in range(30):
            l, g = step(vals)
            vals = jax.tree_util.tree_map(lambda p, gr: p - 0.1 * gr,
                                          vals, g)
            hist.append(float(l))
        assert hist[-1] < hist[0] * 0.6, (hist[0], hist[-1])

    def test_ep_sharded_train_step(self, rng):
        """Experts sharded over the expert axis: param_shardings apply
        and the jitted train step runs under GSPMD."""
        mesh = place.make_mesh((2, 4),
                               (place.AXIS_DATA, place.AXIS_EXPERT))
        cfg = self.MOE_CFG
        params = transformer.init_params(jax.random.PRNGKey(0), cfg)
        sh = transformer.param_shardings(cfg, mesh)
        params = jax.tree_util.tree_map(jax.device_put, params, sh)
        toks = jnp.asarray(rng.randint(0, 50, (4, 16)).astype(np.int32))
        tgt = jnp.asarray(rng.randint(0, 50, (4, 16)).astype(np.int32))

        @jax.jit
        def step(p):
            return jax.value_and_grad(
                lambda p_: transformer.lm_loss(p_, toks, tgt, cfg,
                                               mesh=mesh))(p)

        l, g = step(params)
        assert np.isfinite(float(l))
        chex = jax.tree_util.tree_structure(g)
        assert chex == jax.tree_util.tree_structure(params)

    def test_moe_decode_matches_forward(self, rng):
        """KV-cache decode with the MoE FFN reproduces the full forward
        (decode capacity = batch, so no token drops at inference)."""
        cfg = dataclasses.replace(self.MOE_CFG, d_model=16, n_heads=2,
                                  d_ff=32, max_len=24)
        params = transformer.init_params(jax.random.PRNGKey(0), cfg)
        B, T = 2, 8
        toks = jnp.asarray(rng.randint(0, 50, (B, T)).astype(np.int32))
        want = transformer.forward(params, toks, cfg)
        cache = transformer.init_cache(cfg, B, 16)
        for t in range(T):
            logits, cache = transformer.decode_step(
                params, cache, toks[:, t], jnp.asarray(t, jnp.int32),
                cfg)
            np.testing.assert_allclose(
                np.asarray(logits), np.asarray(want[:, t]), rtol=2e-4,
                atol=2e-4)

    def test_moe_composes_with_layer_remat(self, rng):
        """MoE FFN + layer-granular stash remat: q8_remat's vjp covers
        every block output generically (the aux scalar included), so the
        capacity lever composes with the expert family.

        What the old assert got wrong (it was the last env-sensitive
        tier-1 flake): it bounded the PER-LEAF max relative error of the
        q8 grads at 0.05, but the q8 backward linearizes each block at
        x̃ = dequant(stash), and a stash perturbation (≤ 0.5/127 of the
        tensor absmax, ops/q8.py) can flip a near-tie top-k ROUTING
        decision in the recomputed gate — an O(1), perfectly correct
        divergence on the few affected rows whose magnitude depends on
        backend rounding. Deterministic restructure:

        1. the remat/MoE COMPOSITION machinery (every output's cotangent
           threaded, aux edge included) is checked on the bf16 stash,
           whose ~2^-9 cast noise cannot flip routing at these margins;
        2. the q8 stash is checked with a GLOBAL metric (relative L2
           over the concatenated grads + descent-direction cosine) whose
           tolerance is derived from the documented stash noise: a few
           flipped tokens among B*T=64 move the global L2 by O(k/64),
           not O(1), while a broken vjp (dropped edge, zeroed cotangent)
           still fails by orders of magnitude."""
        cfg_d = dataclasses.replace(self.MOE_CFG)
        params = transformer.init_params(jax.random.PRNGKey(0), cfg_d)
        toks = jnp.asarray(rng.randint(0, 50, (4, 16)).astype(np.int32))
        tgt = jnp.asarray(rng.randint(0, 50, (4, 16)).astype(np.int32))

        def grad_of(cfg):
            return jax.value_and_grad(
                lambda p: transformer.lm_loss(p, toks, tgt, cfg))(params)

        def flat(g):
            return jnp.concatenate(
                [l.reshape(-1).astype(jnp.float32)
                 for l in jax.tree_util.tree_leaves(g)])

        ld, gd = grad_of(cfg_d)
        fd = flat(gd)

        # (1) machinery, deterministically: bf16 stash. The PER-LEAF
        # check survives here (it would catch a vjp regression confined
        # to a small leaf, e.g. a zeroed gate cotangent, that a global
        # metric dilutes away) — bf16's tiny cast noise makes it stable.
        lb, gb = grad_of(dataclasses.replace(self.MOE_CFG, remat="bf16"))
        np.testing.assert_allclose(float(ld), float(lb), rtol=1e-6)
        fb = flat(gb)
        rel_l2_b = float(jnp.linalg.norm(fb - fd)
                         / (jnp.linalg.norm(fd) + 1e-12))
        assert rel_l2_b < 0.02, f"bf16 remat grad divergence {rel_l2_b}"
        worst_leaf = max(
            float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-8))
            for a, b in zip(jax.tree_util.tree_leaves(gb),
                            jax.tree_util.tree_leaves(gd)))
        assert worst_leaf < 0.05, f"bf16 per-leaf divergence {worst_leaf}"

        # (2) q8 stash: forward exact, backward within the noise budget
        lr, gr = grad_of(dataclasses.replace(self.MOE_CFG, remat="q8"))
        np.testing.assert_allclose(float(ld), float(lr), rtol=1e-6)
        fr = flat(gr)
        rel_l2 = float(jnp.linalg.norm(fr - fd)
                       / (jnp.linalg.norm(fd) + 1e-12))
        cos = float(jnp.dot(fr, fd)
                    / (jnp.linalg.norm(fr) * jnp.linalg.norm(fd) + 1e-12))
        # budget: per-block linearization offset ≤ 0.5/127 (≈0.4%) of
        # the block input's absmax, amplified through 2 blocks' worth of
        # nonlinearities plus worst-case routing flips on a handful of
        # the 64 tokens — two orders of magnitude below a broken-vjp
        # failure (rel_l2 ~ 1, cos ~ 0)
        assert rel_l2 < 0.30, f"q8 remat global grad divergence {rel_l2}"
        assert cos > 0.95, f"q8 remat grads left the descent cone: {cos}"


class TestGenerate:
    CFG = transformer.TransformerConfig(
        vocab=50, d_model=16, n_layers=2, n_heads=2, d_ff=32, max_len=24,
        dtype=jnp.float32)

    def test_decode_matches_forward_teacher_forcing(self, rng):
        """KV-cache incremental decode must reproduce the full forward's
        logits position by position (the correctness bar for any cache)."""
        cfg = self.CFG
        params = transformer.init_params(jax.random.PRNGKey(0), cfg)
        B, T = 2, 8
        toks = jnp.asarray(rng.randint(0, cfg.vocab, (B, T)), jnp.int32)
        full = transformer.forward(params, toks, cfg)        # [B, T, V]
        cache = transformer.init_cache(cfg, B, T)
        for t in range(T):
            step_logits, cache = transformer.decode_step(
                params, cache, toks[:, t], jnp.asarray(t, jnp.int32), cfg)
            np.testing.assert_allclose(
                np.asarray(step_logits), np.asarray(full[:, t]),
                rtol=2e-4, atol=2e-4, err_msg=f"position {t}")

    def test_prefill_matches_forward_last_position(self, rng):
        cfg = self.CFG
        params = transformer.init_params(jax.random.PRNGKey(1), cfg)
        B, T = 2, 6
        toks = jnp.asarray(rng.randint(0, cfg.vocab, (B, T)), jnp.int32)
        full = transformer.forward(params, toks, cfg)
        logits, cache = transformer.prefill(params, toks, cfg, T + 4)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(full[:, -1]),
                                   rtol=2e-4, atol=2e-4)
        assert cache["k"].shape == (2, B, T + 4, 2, 8)

    def test_greedy_generate_matches_stepwise_argmax(self, rng):
        """generate(temperature=0) must equal the naive loop that reruns
        the full forward and takes argmax each step."""
        cfg = self.CFG
        params = transformer.init_params(jax.random.PRNGKey(2), cfg)
        B, Tp, new = 2, 5, 6
        prompt = jnp.asarray(rng.randint(0, cfg.vocab, (B, Tp)), jnp.int32)
        got = transformer.generate(params, prompt, cfg, max_new=new)
        assert got.shape == (B, Tp + new)
        ref = prompt
        for _ in range(new):
            logits = transformer.forward(params, ref, cfg)
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            ref = jnp.concatenate([ref, nxt[:, None]], axis=1)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    def test_sampling_reproducible_and_bounded(self, rng):
        cfg = self.CFG
        params = transformer.init_params(jax.random.PRNGKey(3), cfg)
        prompt = jnp.asarray(rng.randint(0, cfg.vocab, (1, 4)), jnp.int32)
        a = transformer.generate(params, prompt, cfg, max_new=5,
                                 temperature=1.0, key=jax.random.PRNGKey(9))
        b = transformer.generate(params, prompt, cfg, max_new=5,
                                 temperature=1.0, key=jax.random.PRNGKey(9))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert int(np.asarray(a).max()) < cfg.vocab
        with pytest.raises(ValueError, match="needs a key"):
            transformer.generate(params, prompt, cfg, max_new=2,
                                 temperature=0.5)
        with pytest.raises(ValueError, match="max_len"):
            transformer.generate(params, prompt, cfg, max_new=100)


class TestBeamSearch:
    CFG = transformer.TransformerConfig(
        vocab=20, d_model=16, n_layers=2, n_heads=2, d_ff=32, max_len=20,
        dtype=jnp.float32)

    def _score_of(self, params, cfg, seq, Tp):
        """Recompute a hypothesis's logprob with the plain forward."""
        logits = transformer.forward(params, seq[None, :-1], cfg)
        lp = jax.nn.log_softmax(logits, axis=-1)[0]
        tgt = seq[Tp:]
        pos = jnp.arange(Tp - 1, Tp - 1 + tgt.shape[0])
        return float(jnp.sum(lp[pos, tgt]))

    def test_scores_match_forward_recompute(self, rng):
        """Every returned hypothesis's reported score must equal the sum
        of stepwise log-probs under the plain forward — this pins both
        the lineage backtracking and the score accumulation."""
        cfg = self.CFG
        params = transformer.init_params(jax.random.PRNGKey(0), cfg)
        B, Tp, new, K = 2, 4, 5, 3
        prompt = jnp.asarray(rng.randint(0, cfg.vocab, (B, Tp)), jnp.int32)
        seqs, scores = transformer.beam_search(params, prompt, cfg,
                                               max_new=new, beam_size=K)
        assert seqs.shape == (B, K, Tp + new)
        for b in range(B):
            # scores descending
            s = np.asarray(scores[b])
            assert (np.diff(s) <= 1e-6).all(), s
            for j in range(K):
                want = self._score_of(params, cfg, seqs[b, j], Tp)
                np.testing.assert_allclose(float(scores[b, j]), want,
                                           rtol=2e-4, atol=2e-3)

    def test_beam1_equals_greedy(self, rng):
        cfg = self.CFG
        params = transformer.init_params(jax.random.PRNGKey(1), cfg)
        prompt = jnp.asarray(rng.randint(0, cfg.vocab, (2, 3)), jnp.int32)
        beam, _ = transformer.beam_search(params, prompt, cfg, max_new=6,
                                          beam_size=1)
        greedy = transformer.generate(params, prompt, cfg, max_new=6)
        np.testing.assert_array_equal(np.asarray(beam[:, 0]),
                                      np.asarray(greedy))

    def test_beam_at_least_as_good_as_greedy(self, rng):
        cfg = self.CFG
        params = transformer.init_params(jax.random.PRNGKey(2), cfg)
        Tp, new = 3, 6
        prompt = jnp.asarray(rng.randint(0, cfg.vocab, (1, Tp)), jnp.int32)
        _, scores = transformer.beam_search(params, prompt, cfg,
                                            max_new=new, beam_size=4)
        greedy = transformer.generate(params, prompt, cfg, max_new=new)
        gs = self._score_of(params, cfg, greedy[0], Tp)
        assert float(scores[0, 0]) >= gs - 1e-4


class TestDropout:
    CFG = transformer.TransformerConfig(
        vocab=30, d_model=16, n_layers=2, n_heads=2, d_ff=32, max_len=16,
        dtype=jnp.float32, dropout=0.5)

    def test_no_key_is_deterministic_and_matches_rate0(self, rng):
        """Without a dropout_key the forward is the eval path — identical
        to a dropout=0 config (serving/eval can't silently drop)."""
        import dataclasses as dc
        params = transformer.init_params(jax.random.PRNGKey(0), self.CFG)
        toks = jnp.asarray(rng.randint(0, 30, (2, 8)), jnp.int32)
        a = transformer.forward(params, toks, self.CFG)
        b = transformer.forward(params, toks,
                                dc.replace(self.CFG, dropout=0.0))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_keys_randomize_and_reproduce(self, rng):
        params = transformer.init_params(jax.random.PRNGKey(0), self.CFG)
        toks = jnp.asarray(rng.randint(0, 30, (2, 8)), jnp.int32)
        k1, k2 = jax.random.PRNGKey(1), jax.random.PRNGKey(2)
        a1 = transformer.forward(params, toks, self.CFG, dropout_key=k1)
        a2 = transformer.forward(params, toks, self.CFG, dropout_key=k1)
        b = transformer.forward(params, toks, self.CFG, dropout_key=k2)
        np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
        assert np.abs(np.asarray(a1) - np.asarray(b)).max() > 0

    def test_grads_flow_with_dropout(self, rng):
        params = transformer.init_params(jax.random.PRNGKey(0), self.CFG)
        toks = jnp.asarray(rng.randint(0, 30, (2, 8)), jnp.int32)
        tgts = jnp.roll(toks, -1, axis=1)
        g = jax.grad(lambda p: transformer.lm_loss(
            p, toks, tgts, self.CFG,
            dropout_key=jax.random.PRNGKey(3)))(params)
        assert all(np.isfinite(np.asarray(x)).all()
                   for x in jax.tree.leaves(g))
        assert float(jnp.abs(g["blocks"]["qkv"]).max()) > 0


class TestTransformerCheckpoint:
    def test_roundtrip_preserves_generation(self, rng, tmp_path):
        """Functional-model serving flow: train a few steps, checkpoint
        the pytree, reload into fresh buffers, and greedy generation must
        be token-identical (the io/checkpoint pytree path + KV-cache
        decode integration)."""
        from paddle_tpu import optimizer as popt
        from paddle_tpu.io import checkpoint as ckpt

        cfg = transformer.TransformerConfig(
            vocab=40, d_model=16, n_layers=2, n_heads=2, d_ff=32,
            max_len=24, dtype=jnp.float32)
        params = transformer.init_params(jax.random.PRNGKey(0), cfg)
        adam = popt.Adam(learning_rate=1e-2)
        ost = adam.tree_init_state(params)
        toks = jnp.asarray(rng.randint(0, 40, (4, 12)), jnp.int32)
        tgts = jnp.roll(toks, -1, axis=1)
        for i in range(3):
            _, g = jax.value_and_grad(transformer.lm_loss)(
                params, toks, tgts, cfg)
            params, ost = adam.tree_update(jnp.asarray(i, jnp.int32), g,
                                           params, ost)
        path = ckpt.save_checkpoint(str(tmp_path), 3, params,
                                    opt_state=ost)
        prompt = toks[:1, :5]
        want = transformer.generate(params, prompt, cfg, max_new=6)

        fresh = transformer.init_params(jax.random.PRNGKey(99), cfg)
        fost = adam.tree_init_state(fresh)
        step, loaded, lost, _ = ckpt.load_checkpoint(
            ckpt.latest_checkpoint(str(tmp_path)), fresh, opt_state=fost)
        assert step == 3
        got = transformer.generate(loaded, prompt, cfg, max_new=6)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        # optimizer state restored too (training can resume)
        la, lb = jax.tree.leaves(ost), jax.tree.leaves(lost)
        assert any(float(jnp.abs(a).max()) > 0 for a in la)
        for a, b in zip(la, lb):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b))


class TestRoPE:
    CFG = transformer.TransformerConfig(
        vocab=30, d_model=16, n_layers=2, n_heads=2, d_ff=32, max_len=24,
        dtype=jnp.float32, use_rope=True)

    def test_decode_matches_forward(self, rng):
        """The KV cache must hold ROTATED keys so incremental decode
        reproduces the full forward under RoPE too."""
        cfg = self.CFG
        params = transformer.init_params(jax.random.PRNGKey(0), cfg)
        B, T = 2, 9
        toks = jnp.asarray(rng.randint(0, 30, (B, T)), jnp.int32)
        full = transformer.forward(params, toks, cfg)
        cache = transformer.init_cache(cfg, B, T)
        for t in range(T):
            logits, cache = transformer.decode_step(
                params, cache, toks[:, t], jnp.asarray(t, jnp.int32), cfg)
            np.testing.assert_allclose(np.asarray(logits),
                                       np.asarray(full[:, t]),
                                       rtol=2e-4, atol=2e-4,
                                       err_msg=f"position {t}")

    def test_relative_shift_invariance(self, rng):
        """The defining RoPE property, checked directly: the q·k score
        between two positions depends only on their OFFSET —
        dot(rope(q, p+s), rope(k, p'+s)) == dot(rope(q, p), rope(k, p'))
        for any shift s. (The causal prefix property alone would pass
        even with a broken rotation.)"""
        Dh = 8
        q = jnp.asarray(rng.randn(1, 1, 1, Dh).astype(np.float32))
        k = jnp.asarray(rng.randn(1, 1, 1, Dh).astype(np.float32))

        def score(pq, pk):
            tq = transformer._rope_tables(
                jnp.asarray([pq], jnp.int32), Dh, 10000.0)
            tk = transformer._rope_tables(
                jnp.asarray([pk], jnp.int32), Dh, 10000.0)
            return float(jnp.sum(transformer._rope(q, tq) *
                                 transformer._rope(k, tk)))

        base = score(3, 1)
        for shift in (1, 5, 11):
            np.testing.assert_allclose(score(3 + shift, 1 + shift), base,
                                       rtol=1e-5)
        # and a DIFFERENT offset gives a different score
        assert abs(score(4, 1) - base) > 1e-4

    def test_odd_head_dim_rejected(self):
        with pytest.raises(ValueError, match="even head_dim"):
            transformer._rope_tables(jnp.asarray([0], jnp.int32), 9,
                                     10000.0)

    def test_generate_and_beam_run(self, rng):
        """Greedy decode equals beam_size=1 EXACTLY under RoPE — both
        paths break logit ties stably toward the lower token id (argmax
        and top_k share that contract), so this holds even on a
        random-init toy model with near-tied logits. Against a wider
        beam only the SCORE ordering is an invariant: beam-2 may
        legitimately out-score the greedy path (that was the old
        flaky assert — greedy == beam-2's best is not a theorem)."""
        cfg = self.CFG
        params = transformer.init_params(jax.random.PRNGKey(2), cfg)
        prompt = jnp.asarray(rng.randint(0, 30, (1, 4)), jnp.int32)
        g = transformer.generate(params, prompt, cfg, max_new=5)
        b1, _ = transformer.beam_search(params, prompt, cfg, max_new=5,
                                        beam_size=1)
        np.testing.assert_array_equal(np.asarray(g),
                                      np.asarray(b1[:, 0]))
        b2, s2 = transformer.beam_search(params, prompt, cfg, max_new=5,
                                         beam_size=2)
        assert b2.shape == (1, 2, 9) and s2.shape == (1, 2)
        # beam-2's best hypothesis scores at least the greedy path
        logits = transformer.forward(params, g[:, :-1], cfg)
        lp = jax.nn.log_softmax(logits, axis=-1)[0]
        pos = jnp.arange(3, 8)
        greedy_score = float(jnp.sum(lp[pos, g[0, 4:]]))
        assert float(s2[0, 0]) >= greedy_score - 1e-4

    def test_ring_flash_matches_full_under_rope(self, rng):
        """RoPE applies before the attention engine, so ring+flash CP
        must agree with single-device full attention bit-for-bit-ish."""
        import dataclasses as dc
        cfg = dc.replace(self.CFG, use_ring_attention=True,
                         use_flash_attention=True, max_len=32)
        mesh = place.make_mesh((1, 2, 1), (place.AXIS_DATA, place.AXIS_SEQ,
                                           place.AXIS_MODEL))
        params = transformer.init_params(jax.random.PRNGKey(3), cfg)
        toks = jnp.asarray(rng.randint(0, 30, (2, 32)), jnp.int32)
        ref_cfg = dc.replace(cfg, use_ring_attention=False,
                             use_flash_attention=False)
        want = transformer.forward(params, toks, ref_cfg)
        got = transformer.forward(params, toks, cfg, mesh=mesh)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)


class TestGQA:
    CFG = transformer.TransformerConfig(
        vocab=30, d_model=16, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=32, max_len=20, dtype=jnp.float32, use_rope=True)

    def test_decode_matches_forward(self, rng):
        """Grouped-query attention: the Hkv-head cache must reproduce the
        full forward (which repeats kv heads for the engines)."""
        cfg = self.CFG
        params = transformer.init_params(jax.random.PRNGKey(0), cfg)
        B, T = 2, 8
        toks = jnp.asarray(rng.randint(0, 30, (B, T)), jnp.int32)
        full = transformer.forward(params, toks, cfg)
        cache = transformer.init_cache(cfg, B, T)
        assert cache["k"].shape == (2, B, T, 2, 4)   # Hkv=2 not H=4
        for t in range(T):
            logits, cache = transformer.decode_step(
                params, cache, toks[:, t], jnp.asarray(t, jnp.int32), cfg)
            np.testing.assert_allclose(np.asarray(logits),
                                       np.asarray(full[:, t]),
                                       rtol=2e-4, atol=2e-4,
                                       err_msg=f"position {t}")

    def test_cache_half_the_size_and_generate_runs(self, rng):
        import dataclasses as dc
        cfg = self.CFG
        mha = dc.replace(cfg, n_kv_heads=0)
        gq = transformer.init_cache(cfg, 1, 16)
        mh = transformer.init_cache(mha, 1, 16)
        assert gq["k"].size * 2 == mh["k"].size
        params = transformer.init_params(jax.random.PRNGKey(1), cfg)
        prompt = jnp.asarray(rng.randint(0, 30, (1, 4)), jnp.int32)
        out = transformer.generate(params, prompt, cfg, max_new=5)
        assert out.shape == (1, 9)

    def test_invalid_ratio_rejected(self):
        cfg = transformer.TransformerConfig(vocab=10, d_model=16,
                                            n_heads=4, n_kv_heads=3)
        with pytest.raises(ValueError, match="multiple"):
            transformer.init_params(jax.random.PRNGKey(0), cfg)

    def test_lm_learns_with_gqa(self, rng):
        cfg = self.CFG
        params = transformer.init_params(jax.random.PRNGKey(2), cfg)
        toks = jnp.asarray((np.arange(16)[None, :] +
                            rng.randint(0, 30, (4, 1))) % 30, jnp.int32)
        tgts = (toks + 1) % 30
        step = jax.jit(jax.value_and_grad(
            lambda p: transformer.lm_loss(p, toks, tgts, cfg)))
        hist = []
        for _ in range(25):
            l, g = step(params)
            params = jax.tree.map(lambda p, gr: p - 0.1 * gr, params, g)
            hist.append(float(l))
        assert hist[-1] < hist[0] * 0.6, (hist[0], hist[-1])


class TestAllToAllAttention:
    """Ulysses-style CP: all-to-all head-scatter instead of the K/V
    ring — must match full attention exactly, GQA included."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_full_attention(self, rng, causal):
        mesh = place.make_mesh((2, 4), (place.AXIS_DATA, place.AXIS_SEQ))
        B, T, H, D = 4, 16, 4, 8
        q = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
        k = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
        v = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
        lens = jnp.asarray(np.array([16, 9, 12, 5], np.int32))
        got = ring.alltoall_attention_spmd(q, k, v, mesh, causal=causal,
                                           lengths=lens)
        want = ring.full_attention(q, k, v, causal=causal, lengths=lens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("use_flash", [False, True])
    def test_grads_and_gqa(self, rng, use_flash):
        mesh = place.make_mesh((1, 4), (place.AXIS_DATA, place.AXIS_SEQ))
        B, T, H, Hkv, D = 2, 16, 8, 4, 4
        q = rng.randn(B, T, H, D).astype(np.float32)
        k = rng.randn(B, T, Hkv, D).astype(np.float32)
        v = rng.randn(B, T, Hkv, D).astype(np.float32)

        def loss_a2a(q_, k_, v_):
            return jnp.sum(ring.alltoall_attention_spmd(
                jnp.asarray(q_), jnp.asarray(k_), jnp.asarray(v_), mesh,
                causal=True, use_flash=use_flash, interpret=True) ** 2)

        def loss_full(q_, k_, v_):
            return jnp.sum(ring.full_attention(
                jnp.asarray(q_), jnp.asarray(k_), jnp.asarray(v_),
                causal=True) ** 2)

        got = ring.alltoall_attention_spmd(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), mesh,
            causal=True, use_flash=use_flash, interpret=True)
        want = ring.full_attention(jnp.asarray(q), jnp.asarray(k),
                                   jnp.asarray(v), causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        g_a = jax.grad(loss_a2a, argnums=(0, 1, 2))(q, k, v)
        g_f = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", g_a, g_f):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=5e-5,
                                       err_msg=f"d{name}")

    def test_rejects_nondividing_heads(self, rng):
        mesh = place.make_mesh((1, 4), (place.AXIS_DATA, place.AXIS_SEQ))
        x = jnp.zeros((2, 16, 6, 4), jnp.float32)   # 6 heads, P=4
        with pytest.raises(ValueError, match="must divide"):
            ring.alltoall_attention_spmd(x, x, x, mesh, causal=True)

    def test_transformer_cp_mode_alltoall(self, rng):
        import dataclasses as dc
        mesh = place.make_mesh((2, 4), (place.AXIS_DATA, place.AXIS_SEQ))
        cfg = dc.replace(CFG, use_ring_attention=True,
                         cp_mode="alltoall", max_len=32)
        params = transformer.init_params(jax.random.PRNGKey(0), cfg)
        toks = jnp.asarray(rng.randint(0, 50, (2, 32)).astype(np.int32))
        got = transformer.forward(params, toks, cfg, mesh=mesh)
        ref_cfg = dc.replace(cfg, use_ring_attention=False)
        want = transformer.forward(params, toks, ref_cfg)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-3, atol=1e-4)

    def test_head_axis_tp_composes(self, rng):
        """dp x sp x tp mesh: heads shard over model, scatter over seq —
        still exact."""
        mesh = place.make_mesh(
            (2, 2, 2), (place.AXIS_DATA, place.AXIS_SEQ, place.AXIS_MODEL))
        B, T, H, D = 2, 16, 8, 4
        q = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
        k = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
        v = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
        got = jax.jit(lambda a, b, c: ring.alltoall_attention_spmd(
            a, b, c, mesh, causal=True))(q, k, v)
        want = ring.full_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_cp_mode_validated(self):
        import dataclasses as dc
        import pytest as pt
        with pt.raises(ValueError, match="cp_mode"):
            dc.replace(CFG, cp_mode="ulysses")


class TestLayerRemat:
    """cfg.remat: layer-granular recompute with a (quantized) stash of
    each block's input (ops/q8.q8_remat) — the long-context capacity
    lever. Forward must be EXACT (the stash is backward-only); grads
    match to stash tolerance; the fwd+bwd temp footprint shrinks."""

    def _setup(self, max_len=64, T=32):
        cfg = transformer.TransformerConfig(
            vocab=64, d_model=32, n_heads=4, n_layers=3, d_ff=64,
            max_len=max_len, dtype=jnp.float32)
        params = transformer.init_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.RandomState(0)
        toks = jnp.asarray(rng.randint(0, 64, (2, T)).astype(np.int32))
        tgt = jnp.asarray(rng.randint(0, 64, (2, T)).astype(np.int32))
        return cfg, params, toks, tgt

    @pytest.mark.parametrize("mode,tol", [("bf16", 0.02), ("q8", 0.08)])
    def test_forward_exact_grads_close(self, mode, tol):
        cfg, params, toks, tgt = self._setup()
        ref_l, ref_g = jax.value_and_grad(transformer.lm_loss)(
            params, toks, tgt, cfg)
        rcfg = dataclasses.replace(cfg, remat=mode)
        loss, g = jax.value_and_grad(transformer.lm_loss)(
            params, toks, tgt, rcfg)
        assert float(loss) == float(ref_l), "remat changed the forward"
        worst = max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
            lambda a, b: float(jnp.abs(a - b).max()
                               / (jnp.abs(b).max() + 1e-9)), g, ref_g)))
        assert worst < tol, f"remat={mode} worst grad rel err {worst}"

    def test_temp_memory_shrinks(self):
        cfg, params, _, _ = self._setup(max_len=512, T=512)
        rng = np.random.RandomState(1)
        toks = jnp.asarray(rng.randint(0, 64, (2, 512)).astype(np.int32))

        def temp(mode):
            c = dataclasses.replace(cfg, remat=mode)
            f = jax.jit(lambda p, t, g: jax.value_and_grad(
                transformer.lm_loss)(p, t, g, c))
            return f.lower(params, toks,
                           toks).compile().memory_analysis().temp_size_in_bytes

        none, q8r = temp("none"), temp("q8")
        assert q8r < 0.5 * none, (none, q8r)

    def test_composes_with_ring_flash(self):
        """remat=q8 under ring-CP + flash on the seq mesh trains."""
        mesh = place.make_mesh((1, 8, 1), (place.AXIS_DATA, place.AXIS_SEQ,
                                           place.AXIS_MODEL))
        cfg = transformer.TransformerConfig(
            vocab=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
            max_len=64, dtype=jnp.float32, use_ring_attention=True,
            use_flash_attention=True, remat="q8")
        params = transformer.init_params(jax.random.PRNGKey(0), cfg)
        sharded = jax.tree_util.tree_map(
            jax.device_put, params, transformer.param_shardings(cfg, mesh))
        rng = np.random.RandomState(0)
        toks = jnp.asarray(rng.randint(0, 64, (1, 64)).astype(np.int32))

        @jax.jit
        def step(p, tk):
            loss, g = jax.value_and_grad(transformer.lm_loss)(
                p, tk, tk, cfg, mesh=mesh)
            return loss, jax.tree_util.tree_map(
                lambda w, gr: w - 0.1 * gr, p, g)

        l1, p2 = step(sharded, toks)
        l2, _ = step(p2, toks)
        assert float(l2) < float(l1)


class TestWireInt8:
    """int8 wire codecs for the distributed sends (ops/q8
    make_ppermute_q8): ring-CP K/V rotations and pipeline inter-stage
    activations travel as int8 + per-shard scales, both directions."""

    def test_ring_attention_wire_int8_close(self, rng):
        mesh = place.make_mesh((1, 8), (place.AXIS_DATA, place.AXIS_SEQ))
        B, T, H, D = 2, 32, 2, 8
        q = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32)) * 0.5
        k = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32)) * 0.5
        v = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
        ref = ring.ring_attention_spmd(q, k, v, mesh, causal=True)
        got = ring.ring_attention_spmd(q, k, v, mesh, causal=True,
                                       wire_int8=True)
        rel = float(jnp.abs(got - ref).max() / (jnp.abs(ref).max() + 1e-9))
        assert rel < 0.05, f"wire-int8 ring rel err {rel}"

    def test_ring_wire_int8_grads_flow(self, rng):
        mesh = place.make_mesh((1, 8), (place.AXIS_DATA, place.AXIS_SEQ))
        B, T, H, D = 1, 16, 2, 4
        q = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))

        def loss(q_, k_, v_):
            return jnp.sum(ring.ring_attention_spmd(
                q_, k_, v_, mesh, causal=True, wire_int8=True) ** 2)

        gq, gk, gv = jax.grad(loss, argnums=(0, 1, 2))(q, q, q)
        for g in (gq, gk, gv):
            assert jnp.isfinite(g).all()
            assert float(jnp.abs(g).max()) > 0

    def test_flash_ring_wire_int8_close(self, rng):
        """The flash engine's K/V hops (fwd and bwd re-walk) use the
        codec too; grads stay close to the full-precision flash ring."""
        mesh = place.make_mesh((1, 8), (place.AXIS_DATA, place.AXIS_SEQ))
        B, T, H, D = 1, 64, 2, 8
        q = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32)) * 0.5

        def loss(wire):
            def f(q_, k_, v_):
                return jnp.sum(ring.ring_attention_spmd(
                    q_, k_, v_, mesh, causal=True, use_flash=True,
                    wire_int8=wire) ** 2)
            return f

        ref = ring.ring_attention_spmd(q, q, q, mesh, causal=True,
                                       use_flash=True)
        got = ring.ring_attention_spmd(q, q, q, mesh, causal=True,
                                       use_flash=True, wire_int8=True)
        rel = float(jnp.abs(got - ref).max() / (jnp.abs(ref).max() + 1e-9))
        assert rel < 0.05, f"flash wire-int8 fwd rel err {rel}"
        g_ref = jax.grad(loss(False), argnums=(0, 1, 2))(q, q, q)
        g_got = jax.grad(loss(True), argnums=(0, 1, 2))(q, q, q)
        for name, a, b in zip("dq dk dv".split(), g_got, g_ref):
            r = float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-9))
            assert r < 0.08, f"flash wire-int8 {name} rel err {r}"

    def test_pipeline_wire_int8_trains(self, rng):
        from paddle_tpu.parallel import pipeline
        mesh = place.make_mesh((4,), (place.AXIS_STAGE,))
        S, D, B, M = 4, 8, 16, 4
        params = {"w": jnp.asarray(rng.randn(S, D, D).astype(np.float32)
                                   * 0.3),
                  "b": jnp.zeros((S, D), jnp.float32)}
        x = jnp.asarray(rng.randn(B, D).astype(np.float32))
        y = jnp.asarray(rng.randn(B, D).astype(np.float32) * 0.1)

        def stage_fn(p, h):
            return jnp.tanh(h @ p["w"] + p["b"])

        ref = pipeline.pipeline_apply(params, x, stage_fn, mesh, M)
        got = pipeline.pipeline_apply(params, x, stage_fn, mesh, M,
                                      wire_int8=True)
        rel = float(jnp.abs(got - ref).max() / (jnp.abs(ref).max() + 1e-9))
        assert rel < 0.05, f"wire-int8 pipeline rel err {rel}"

        @jax.jit
        def train_step(p):
            def loss(p_):
                out = pipeline.pipeline_apply(p_, x, stage_fn, mesh, M,
                                              wire_int8=True)
                return jnp.mean((out - y) ** 2)
            l, g = jax.value_and_grad(loss)(p)
            return l, jax.tree_util.tree_map(lambda w, gr: w - 0.2 * gr,
                                             p, g)

        l1, p2 = train_step(params)
        l2, _ = train_step(p2)
        assert float(l2) < float(l1)

    def test_int8_actually_crosses_the_wire(self):
        """HLO-level guard against silent no-op codecs (the failure mode
        that killed the MoE attempt): the compiled programs must contain
        collective-permutes on s8 operands."""
        import re
        mesh = place.make_mesh((1, 8), (place.AXIS_DATA, place.AXIS_SEQ))
        q = jnp.zeros((1, 32, 2, 8), jnp.float32)
        f = jax.jit(lambda q: ring.ring_attention_spmd(
            q, q, q, mesh, causal=True, wire_int8=True))
        txt = f.lower(q).compile().as_text()
        cp_lines = [l for l in txt.splitlines()
                    if "collective-permute" in l]
        assert any("s8[" in l for l in cp_lines), \
            "ring wire_int8: no int8 collective-permute in compiled HLO"

        from paddle_tpu.parallel import pipeline
        m2 = place.make_mesh((4,), (place.AXIS_STAGE,))
        params = {"w": jnp.zeros((4, 8, 8), jnp.float32),
                  "b": jnp.zeros((4, 8), jnp.float32)}
        x = jnp.zeros((16, 8), jnp.float32)
        g = jax.jit(lambda p, x: pipeline.pipeline_apply(
            p, x, lambda pp, h: jnp.tanh(h @ pp["w"] + pp["b"]),
            m2, 4, wire_int8=True))
        txt2 = g.lower(params, x).compile().as_text()
        cp2 = [l for l in txt2.splitlines() if "collective-permute" in l]
        assert any("s8[" in l for l in cp2), \
            "pipeline wire_int8: no int8 collective-permute in HLO"
