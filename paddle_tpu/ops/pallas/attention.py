"""Flash attention — streaming-softmax fused attention Pallas kernel.

Replaces the materialised [B, H, T, T] score tensor of plain attention
(parallel/ring.py full_attention) with an online-softmax accumulation over
key blocks, so HBM traffic is O(T·D) instead of O(T²) and long sequences
stop being memory-bound (the capability slot of the reference's hand-fused
CUDA attention-precursors, paddle/cuda/src/hl_cuda_sequence.cu; design per
the public FlashAttention recipe on the MXU).

Layout: q/k/v are [B, T, H, D] (the framework's attention layout). The
kernel grids over (batch·heads, query blocks) with an inner
``lax.fori_loop`` over key blocks; running max/denominator live in VMEM
scratch. Backward is a second Pallas kernel gridded over key blocks that
streams query blocks, reconstructing p exactly from the saved logsumexp —
no O(T²) tensor exists in either direction; dq accumulates in an fp32
output revisited across key-block grid steps.

Under ``PADDLE_TPU_PALLAS=off`` (what ``auto`` resolves to off-TPU) the
public entry runs the jnp reference; tests run the kernel in interpret
mode.
"""

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from paddle_tpu.ops.pallas import policy as _policy

NEG_INF = -1e30

# ---------------------------------------------------------------------------
# block-size selection
# ---------------------------------------------------------------------------

# measured-best blocks keyed (seq_bucket, head_dim, dtype_name) — filled
# from on-chip sweeps (benchmarks/tune_flash_blocks.py); consulted before
# the analytic default. seq buckets are powers of two (lookup rounds up).
MEASURED_BLOCKS = {
    # (2048, 64, "float32"): (128, 128) measured 1.58x tokens/sec vs plain
    (2048, 64, "float32"): (128, 128),
    (2048, 64, "bfloat16"): (128, 128),
}


def _vmem_working_set(tp: int, d: int, bq: int, bk: int,
                      itemsize: int) -> int:
    """Upper-bound VMEM residency of one grid program, max over the fwd
    and bwd kernels. fwd holds the whole padded K/V ([tp, d] each) plus a
    q/out block; bwd streams q/do/dq whole ([tp, d] each, dq in fp32)
    against one k/v block. Row stats ride in [tp] fp32 pairs."""
    stats = 2 * tp * 4                        # lse + delta (fp32)
    scores = bq * bk * 4                      # p / ds tile (fp32)
    fwd = (2 * tp * d * itemsize              # k, v whole
           + 2 * bq * d * itemsize            # q, out blocks
           + bq * d * 4                       # fp32 accumulator
           + stats + scores)
    bwd = (2 * tp * d * itemsize              # q, do whole
           + tp * d * 4                       # dq whole (fp32 accumulator)
           + 4 * bk * d * itemsize            # k, v, dk, dv blocks
           + stats + scores)
    return max(fwd, bwd)


def planning_budget(interpret: bool) -> int:
    """VMEM bytes block selection plans against: the target chip's
    budget for a compiled kernel; the interpreter has no VMEM, so it
    plans against the smallest scoped default and picks the blocks a
    real chip would."""
    if interpret:
        return int(_policy.SCOPED_VMEM_DEFAULT_BYTES
                   * _policy.VMEM_PLANNING_SHARE)
    return _policy.vmem_budget_bytes()


def select_block_sizes(seq: int, head_dim: int, dtype,
                       vmem_budget: int) -> Tuple[int, int]:
    """(block_q, block_k) for the flash kernels, keyed on the problem
    shape: a measured table first, then the analytic default (128, 128 —
    the MXU-native tile), always validated against ``vmem_budget``
    bytes (``policy.vmem_budget_bytes()`` of the target chip).
    Raises with a actionable message when no block choice can fit —
    the caller should shard the sequence (ring attention) instead of
    letting Mosaic fail opaquely."""
    itemsize = jnp.dtype(dtype).itemsize
    name = jnp.dtype(dtype).name
    bucket = 1 << max(0, (seq - 1)).bit_length()     # next pow2 >= seq
    found = MEASURED_BLOCKS.get((bucket, head_dim, name))
    candidates = ([found] if found else []) + [(128, 128), (128, 256),
                                               (256, 128), (64, 128),
                                               (128, 64), (64, 64)]
    for bq, bk in candidates:
        bq_c, bk_c = min(bq, seq), min(bk, seq)
        tp = _pad_to_blocks(seq, bq_c, bk_c)
        if _vmem_working_set(tp, head_dim, bq_c, bk_c,
                             itemsize) <= vmem_budget:
            return bq_c, bk_c
    raise ValueError(
        f"flash attention: no block size fits seq={seq} head_dim="
        f"{head_dim} dtype={name} in ~{vmem_budget >> 20} MiB VMEM — the "
        f"whole K/V must reside per grid program. Shard the sequence "
        f"(use_ring_attention over a seq mesh axis) or reduce head_dim.")


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, sm_scale, causal,
                block_q, block_k, seq_len):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * sm_scale          # [block_q, D]
    m = jnp.full((block_q,), NEG_INF, jnp.float32)
    l = jnp.zeros((block_q,), jnp.float32)
    acc = jnp.zeros((block_q, q.shape[-1]), jnp.float32)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    padded_len = k_ref.shape[1]
    num_k = padded_len // block_k
    if causal:
        # only key blocks at or before this query block contribute
        num_k = jax.lax.min(num_k, (qi * block_q + block_q + block_k - 1)
                            // block_k)

    def body(ki, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(ki * block_k, block_k)].astype(jnp.float32)
        v = v_ref[0, pl.ds(ki * block_k, block_k)].astype(jnp.float32)
        s = q @ k.T                                      # [block_q, block_k]
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        valid = k_pos < seq_len                          # mask tail padding
        if causal:
            valid = valid & (q_pos >= k_pos)
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[:, None] + p @ v
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, num_k, body, (m, l, acc))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    # row stats are stored [BH, num_q_blocks, block_q] with block_q on the
    # TPU lane dim — a [T]-shaped output would need a (1, block_q) block,
    # which the (8, 128) tiling rejects, and lane-replicating to 128 wide
    # costs 128x VMEM in the backward's whole-array block. The lse block
    # here spans ALL q-blocks and is revisited consecutively across the
    # inner q grid dim (each program writes its own row), so it flushes
    # once per batch·head.
    lse_ref[0, qi] = m + jnp.log(l_safe)


def _pad_to_blocks(t, block_q, block_k):
    """Common padded length for fwd and bwd — they must agree exactly (the
    backward reconstructs p from the forward's lse), and it must be a
    multiple of BOTH block sizes: the compact row-stats layout reshapes
    [tp] -> [tp // block_q, block_q]."""
    lcm = math.lcm(block_q, block_k)
    return -(-t // lcm) * lcm


def _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    """q/k/v: [BH, T, D] → (out [BH, T, D], lse [BH, T]). T is padded up to
    a block multiple so dynamic slices never clamp; padded keys are masked
    by position, padded query rows are sliced away."""
    bh, t, d = q.shape
    tp = _pad_to_blocks(t, block_q, block_k)
    if tp != t:
        pad = ((0, 0), (0, tp - t), (0, 0))
        q, k, v = (jnp.pad(a, pad) for a in (q, k, v))
    nq = tp // block_q
    grid = (bh, nq)
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_k=block_k, seq_len=t)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, tp, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, tp, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, nq, block_q), lambda b, i: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tp, d), q.dtype),
            jax.ShapeDtypeStruct((bh, nq, block_q), jnp.float32),
        ],
        interpret=interpret,
        **_policy.compiled_kernel_params(
            interpret, _vmem_working_set(tp, d, block_q, block_k,
                                         q.dtype.itemsize),
            "flash_attention"),
    )(q, k, v)
    return out[:, :t], lse.reshape(bh, tp)[:, :t]


def _reference(q, k, v, sm_scale, causal):
    """jnp reference ([BH, T, D] layout), also the off-TPU fallback."""
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if causal:
        t = q.shape[1]
        i = jnp.arange(t)
        s = jnp.where(i[:, None] >= i[None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)
                      ).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    out, _ = _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k,
                        interpret)
    return out


def _flash_vjp_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    out, lse = _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k,
                          interpret)
    return out, (q, k, v, out, lse)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, *, sm_scale, causal, block_q,
                block_k, seq_len):
    """Backward over one KEY block (grid: batch·heads × key blocks).

    Inner loop streams query blocks; p is reconstructed exactly from the
    stored logsumexp, ds from the precomputed delta = Σ(do·out), so no
    [T, T] tensor ever exists. dk/dv accumulate locally; dq accumulates
    into its output ref across key-block grid steps (revisited output
    block — the TPU grid is sequential, so += is race-free); the dq
    output is fp32 so the repeated read-modify-write never rounds in
    bf16."""
    ki = pl.program_id(1)
    k = k_ref[0].astype(jnp.float32)                     # [block_k, D]
    v = v_ref[0].astype(jnp.float32)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    padded_len = q_ref.shape[1]
    num_q = padded_len // block_q
    q_start = (ki * block_k) // block_q if causal else 0

    dk = jnp.zeros_like(k)
    dv = jnp.zeros_like(v)

    def body(qi, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(qi * block_q, block_q)].astype(jnp.float32)
        do = do_ref[0, pl.ds(qi * block_q, block_q)].astype(jnp.float32)
        lse = lse_ref[0, qi]                             # [block_q]
        delta = delta_ref[0, qi]
        s = (q @ k.T) * sm_scale                         # [block_q, block_k]
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        valid = (k_pos < seq_len) & (q_pos < seq_len)
        if causal:
            valid = valid & (q_pos >= k_pos)
        p = jnp.where(valid, jnp.exp(s - lse[:, None]), 0.0)
        dv = dv + p.T @ do
        dp = do @ v.T
        ds = p * (dp - delta[:, None]) * sm_scale
        dq_ref[0, pl.ds(qi * block_q, block_q)] += (ds @ k).astype(
            dq_ref.dtype)
        dk = dk + ds.T @ q
        return dk, dv

    @pl.when(ki == 0)
    def _init():
        dq_ref[0] = jnp.zeros_like(dq_ref[0])

    dk, dv = jax.lax.fori_loop(q_start, num_q, body, (dk, dv))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_bwd_pallas(q, k, v, out, lse, do, sm_scale, causal, block_q,
                      block_k, interpret):
    bh, t, d = q.shape
    tp = _pad_to_blocks(t, block_q, block_k)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                              # [BH, T]
    if tp != t:
        pad3 = ((0, 0), (0, tp - t), (0, 0))
        pad2 = ((0, 0), (0, tp - t))
        q, k, v, do = (jnp.pad(a, pad3) for a in (q, k, v, do))
        # padded lse must stay finite: exp(s - lse) with lse=0 on padded
        # rows is masked out by `valid` anyway
        lse = jnp.pad(lse, pad2)
        delta = jnp.pad(delta, pad2)
    # compact row-stats layout, block_q on the lane dim (see _fwd_kernel)
    nq = tp // block_q
    lse = lse.reshape(bh, nq, block_q)
    delta = delta.reshape(bh, nq, block_q)
    kernel = functools.partial(
        _bwd_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_k=block_k, seq_len=t)
    grid = (bh, tp // block_k)
    dq, dk, dv = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tp, d), lambda b, i: (b, 0, 0)),   # q
            pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0)),  # k
            pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0)),  # v
            pl.BlockSpec((1, tp, d), lambda b, i: (b, 0, 0)),   # do
            pl.BlockSpec((1, nq, block_q), lambda b, i: (b, 0, 0)),  # lse
            pl.BlockSpec((1, nq, block_q), lambda b, i: (b, 0, 0)),  # delta
        ],
        out_specs=[
            pl.BlockSpec((1, tp, d), lambda b, i: (b, 0, 0)),   # dq
            pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0)),  # dk
            pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0)),  # dv
        ],
        out_shape=[
            # dq accumulates across key-block revisits: keep it fp32 so
            # a bf16 read-modify-write chain can't round away increments
            jax.ShapeDtypeStruct((bh, tp, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, tp, d), k.dtype),
            jax.ShapeDtypeStruct((bh, tp, d), v.dtype),
        ],
        interpret=interpret,
        **_policy.compiled_kernel_params(
            interpret, _vmem_working_set(tp, d, block_q, block_k,
                                         q.dtype.itemsize),
            "flash_attention"),
    )(q, k, v, do, lse, delta)
    return dq[:, :t].astype(q.dtype), dk[:, :t], dv[:, :t]


def _flash_vjp_bwd(sm_scale, causal, block_q, block_k, interpret, res, do):
    """Backward from saved (q, k, v, out, lse) — a Pallas kernel streaming
    query blocks per key block, so no O(T²) tensor exists in backward
    either; p/ds reconstruct exactly from the stored logsumexp."""
    q, k, v, out, lse = res
    return _flash_bwd_pallas(q, k, v, out, lse, do, sm_scale, causal,
                             block_q, block_k, interpret)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_block_fwd(q, k, v, sm_scale, causal, block_q=128, block_k=128,
                    interpret=False):
    """Public block-level entry for composed attentions (ring/context
    parallelism): returns (normalized out, logsumexp) for one q-shard
    against one k/v-block, both [BH, T, D]. The caller folds blocks with
    the logsumexp combination rule and drives the backward itself via
    flash_block_bwd (see parallel/ring.ring_flash_attention)."""
    return _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k,
                      interpret)


def flash_block_bwd(q, k, v, out, lse, do, sm_scale, causal,
                    block_q=128, block_k=128, interpret=False):
    """Block-level backward: gradients of sum(out·do) for one q-shard
    against one k/v-block, given the GLOBAL logsumexp (the flash backward
    identity p = exp(s − lse) is exact under any block partition of the
    keys when lse is the all-blocks logsumexp)."""
    return _flash_bwd_pallas(q, k, v, out, lse, do, sm_scale, causal,
                             block_q, block_k, interpret)


def flash_attention(q, k, v, *, causal: bool = True,
                    sm_scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """Fused attention. q: [B, T, H, D], k/v: [B, T, Hkv, D] with
    H % Hkv == 0 → [B, T, H, D].

    Hkv < H (grouped-query attention) is expanded to the q-head layout
    here — a single-device layout concern only; the distributed ring path
    (parallel/ring.py) keeps collectives at Hkv heads and expands locally
    per ring step. Dispatch resolves through the package-wide
    ``PADDLE_TPU_PALLAS`` policy (``ops/pallas/policy.py``): ``auto``
    is the kernel on TPU and the jnp reference elsewhere, while the env
    var (or the ``interpret`` arg, which wins over it: True pins the
    interpreter, False the compiled kernel) can force any path on any
    backend — a compiled kernel the backend or the chip's VMEM cannot
    take raises, it never degrades to the reference."""
    b, t, h, d = q.shape
    if k.shape[2] != h:
        k = jnp.repeat(k, h // k.shape[2], axis=2)
        v = jnp.repeat(v, h // v.shape[2], axis=2)
    sm_scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    qr = q.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    kr = k.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    mode = _policy.pallas_mode(
        None if interpret is None else
        ("interpret" if interpret else "on"))
    _policy.note_path("attention", _policy.kernel_path(mode))
    if mode == "off":
        out = _reference(qr, kr, vr, sm_scale, causal)
    else:
        # shape-keyed selection (measured table + VMEM-fit validation)
        # only when the caller didn't pin blocks — explicit args must
        # keep working on shapes the analytic model would reject
        # (tuning sweeps, CPU interpret runs)
        if block_q and block_k:
            bq, bk = min(block_q, t), min(block_k, t)
        else:
            bq_auto, bk_auto = select_block_sizes(
                t, d, q.dtype, planning_budget(mode == "interpret"))
            bq = min(block_q, t) if block_q else bq_auto
            bk = min(block_k, t) if block_k else bk_auto
        out = _flash(qr, kr, vr, sm_scale, causal, bq, bk,
                     mode == "interpret")
    return out.reshape(b, h, t, d).transpose(0, 2, 1, 3)
