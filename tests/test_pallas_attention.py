"""Flash-attention Pallas kernel vs the full-attention reference — run in
interpret mode on CPU (the kernel itself targets TPU; SURVEY.md §4.7
fake-backend strategy)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import attention as fa
from paddle_tpu.parallel import ring


def make_qkv(rng, b=2, t=64, h=2, d=16):
    mk = lambda: jnp.asarray(rng.randn(b, t, h, d).astype(np.float32) * 0.5)
    return mk(), mk(), mk()


class TestFlashForward:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, rng, causal):
        q, k, v = make_qkv(rng)
        out = fa.flash_attention(q, k, v, causal=causal, interpret=True,
                                 block_q=32, block_k=32)
        ref = ring.full_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_uneven_blocks(self, rng):
        # t=48 with block 32: ragged final block
        q, k, v = make_qkv(rng, t=48)
        out = fa.flash_attention(q, k, v, causal=True, interpret=True,
                                 block_q=32, block_k=32)
        ref = ring.full_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_mismatched_block_sizes(self, rng):
        """block_q != block_k where neither divides the other's round-up:
        the padded length must be a common multiple or the compact
        [nq, block_q] row-stats layout can't hold a [tp] vector
        (regression: t=10, block_q=6, block_k=8 → tp must be 24, not 16)."""
        q, k, v = make_qkv(rng, t=10, d=8)
        out = fa.flash_attention(q, k, v, causal=True, interpret=True,
                                 block_q=6, block_k=8)
        ref = ring.full_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_cpu_fallback_matches(self, rng):
        q, k, v = make_qkv(rng, t=32)
        out = fa.flash_attention(q, k, v, causal=True)  # jnp fallback path
        ref = ring.full_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


class TestFlashBackward:
    def test_grads_match_reference(self, rng):
        q, k, v = make_qkv(rng, b=1, t=32, h=2, d=8)

        def loss_flash(q, k, v):
            o = fa.flash_attention(q, k, v, causal=True, interpret=True,
                                   block_q=16, block_k=16)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        def loss_ref(q, k, v):
            o = ring.full_attention(q, k, v, causal=True)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)


class TestFlashBackwardKernel:
    """The Pallas backward kernel (key-block grid, streamed query blocks)
    vs reference grads — uneven tails, non-causal, bf16."""

    @pytest.mark.parametrize("causal,t", [(True, 48), (False, 40)])
    def test_uneven_grads_match(self, rng, causal, t):
        q, k, v = make_qkv(rng, b=1, t=t, h=2, d=8)

        def loss_flash(q, k, v):
            o = fa.flash_attention(q, k, v, causal=causal, interpret=True,
                                   block_q=16, block_k=16)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        def loss_ref(q, k, v):
            o = ring.full_attention(q, k, v, causal=causal)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)

    def test_bf16_grads_close_to_fp32_reference(self, rng):
        """Multi-block bf16 grads vs the fp32 reference — catches bf16
        accumulation rounding across key-block revisits (dq is fp32
        inside the kernel for exactly this reason)."""
        qf, kf, vf = make_qkv(rng, b=1, t=64, h=1, d=8)
        q, k, v = (a.astype(jnp.bfloat16) for a in (qf, kf, vf))

        def loss(q, k, v):
            o = fa.flash_attention(q, k, v, causal=True, interpret=True,
                                   block_q=16, block_k=16)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        def loss_ref(q, k, v):
            o = ring.full_attention(q, k, v, causal=True)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(qf, kf, vf)
        for a, e in zip(g, gr):
            assert a.dtype == jnp.bfloat16
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(e), rtol=6e-2, atol=6e-2)


class TestBlockSelection:
    """Shape-keyed block-size selection with VMEM-fit validation (no
    hand-tuned constants in the public API path)."""

    # what the interpreter plans against: the smallest scoped default
    BUDGET = fa.planning_budget(interpret=True)

    def test_budget_is_the_target_chips(self):
        from paddle_tpu.ops.pallas import policy
        assert self.BUDGET == int(16 * 2**20 * 0.85)
        with policy.compile_target("TPU v5 lite"):
            assert fa.planning_budget(False) == int(128 * 2**20 * 0.85)
        with policy.compile_target("TPU v99"):
            with pytest.raises(ValueError, match="TPU v99"):
                fa.planning_budget(False)

    def test_measured_table_hit(self):
        bq, bk = fa.select_block_sizes(2048, 64, jnp.float32,
                                       self.BUDGET)
        assert (bq, bk) == fa.MEASURED_BLOCKS[(2048, 64, "float32")]

    def test_default_fits_and_divides(self):
        for seq in (7, 128, 1000, 4096, 8192):
            bq, bk = fa.select_block_sizes(seq, 64, jnp.bfloat16,
                                           self.BUDGET)
            assert bq <= max(seq, 64) and bk <= max(seq, 64)
            tp = fa._pad_to_blocks(seq, bq, bk)
            assert tp % bq == 0 and tp % bk == 0
            assert fa._vmem_working_set(tp, 64, bq, bk, 2) <= self.BUDGET

    def test_long_seq_fp32_prefers_fit(self):
        """seq 16k, D=64: whole-K/V residency must still yield a fitting
        choice in BOTH dtypes, not a crash (fp32 is the stressful one:
        K/V alone are 2·16k·64·4 = 8 MiB)."""
        for dtype, isz in ((jnp.bfloat16, 2), (jnp.float32, 4)):
            bq, bk = fa.select_block_sizes(16384, 64, dtype,
                                           self.BUDGET)
            tp = fa._pad_to_blocks(16384, bq, bk)
            assert fa._vmem_working_set(tp, 64, bq, bk,
                                        isz) <= self.BUDGET, dtype

    def test_unfittable_raises_actionable(self):
        with pytest.raises(ValueError, match="ring_attention"):
            fa.select_block_sizes(1 << 17, 256, jnp.float32,
                                  self.BUDGET)

    def test_auto_selection_matches_reference(self, rng):
        """flash_attention with no block args (auto path) stays exact."""
        q = jnp.asarray(rng.randn(1, 96, 2, 16).astype(np.float32))
        out = fa.flash_attention(q, q, q, causal=True, interpret=True)
        ref = ring.full_attention(q, q, q, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
