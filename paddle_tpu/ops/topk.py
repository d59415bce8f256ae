"""Top-k / argmax ops (reference: paddle/cuda/src/hl_top_k.cu,
operators/top_k_op.cc, gserver MaxIdLayer.cpp). ``lax.top_k`` lowers to
the TPU's sort/partial-sort for a STATIC k. A k that is a traced value
per row (the serving sampler's ``top_k`` vector) needs only the k-th
largest VALUE of each row, and ``kth_largest`` finds it by selection: 32
compare-and-count passes over the row, no sort. Both placements of the
sampler read it: ``serving/sampling.sample_tokens`` over ``[B, V]``
under XLA, and the ``ops/pallas/decode.fused_sample`` kernel over its
``[1, V]`` block under Mosaic, whose limits shape how it is written
(fp32 0/1 counts: no integer reduction, no uint32 -> float cast)."""

import jax
import jax.numpy as jnp
from jax import lax


def top_k(x: jax.Array, k: int):
    """Returns (values, indices) over the last axis."""
    return lax.top_k(x, k)


def max_id(x: jax.Array) -> jax.Array:
    """Argmax over last axis, kept as [..., 1] (reference: MaxIdLayer)."""
    return jnp.argmax(x, axis=-1, keepdims=True).astype(jnp.int32)


def sortable_key(v: jax.Array) -> jax.Array:
    """fp32 -> uint32 order-preserving image (the radix-sort key map):
    positive floats get the sign bit set, negative floats flip every
    bit, so unsigned comparisons order exactly like float compares
    (with ``-0.0`` one below ``+0.0``, where floats call them equal)."""
    u = lax.bitcast_convert_type(v, jnp.uint32)
    flip = ((u >> 31) * jnp.uint32(0x7FFFFFFF)) | jnp.uint32(0x80000000)
    return u ^ flip


def key_value(key: jax.Array) -> jax.Array:
    """The inverse of :func:`sortable_key`: uint32 image -> its fp32."""
    flip = (((key >> 31) ^ jnp.uint32(1)) * jnp.uint32(0x7FFFFFFF)
            | jnp.uint32(0x80000000))
    return lax.bitcast_convert_type(key ^ flip, jnp.float32)


def kth_largest(x: jax.Array, k: jax.Array) -> jax.Array:
    """The k-th largest value of each row: ``x`` [..., V] fp32, ``k``
    traced, int, ``>= 1``, of a shape that broadcasts against
    [..., 1] -> [..., 1] fp32, exactly
    ``sort(x, -1)[..., ::-1][..., k - 1]`` for rows without NaNs, ties
    counted as often as they occur. A 32-step binary search per row on
    the threshold over the integer image of the values:
    ``count(keys >= t)`` is monotone in ``t``, so the invariant
    ``count(>= lo) >= k`` pins ``lo`` to the k-th key once the interval
    collapses, and that key maps back to the one float it came from.
    O(32·V) compares a row and no sort, which Mosaic cannot lower and
    XLA compiles and runs slowly at a vocabulary's width. The count
    sums an fp32 0/1 image — exact below 2^24, far above any
    vocabulary — because integer reductions have no Mosaic lowering."""
    keys = sortable_key(x)
    kf = k.astype(jnp.float32)
    lead = x.shape[:-1] + (1,)

    def body(_, lh):
        lo, hi = lh
        d = hi - lo
        mid = lo + (d >> 1) + (d & jnp.uint32(1))   # ceil, overflow-safe
        cnt = jnp.sum((keys >= mid).astype(jnp.float32), axis=-1,
                      keepdims=True)
        take = cnt >= kf
        return (jnp.where(take, mid, lo),
                jnp.where(take, hi, mid - jnp.uint32(1)))

    lo, _ = lax.fori_loop(
        0, 32, body, (jnp.zeros(lead, jnp.uint32),
                      jnp.full(lead, 0xFFFFFFFF, jnp.uint32)))
    return key_value(lo)
