"""Flash-decode over the paged KV pool + fused sampling epilogue.

The serving engine's per-token hot path (``transformer.decode_step_paged``)
is gather-heavy under XLA: every step materializes a ``[B, T, Hkv, Dh]``
logical KV view out of the block pool, re-reads it for the score einsum,
and keeps a ``[B, H, T]`` score tensor in HBM between softmax stages.
``flash_decode_attention`` is the Pallas replacement, built around the
HEAD-MAJOR pool layout ``[Hkv, M, Dh]`` (kv-head leading — the standard
TPU paged-KV layout ``transformer.init_block_pool`` adopted with it):

- grid ``(slot, kv-head, page-step)``; the page table and per-slot
  positions ride as **scalar-prefetch** operands
  (``pltpu.PrefetchScalarGridSpec``), so each grid step's K/V block is
  PLACED by indexing the pool's BlockSpec through ``pages[b, j]`` —
  Mosaic's DMA engine streams exactly the slot's MAPPED
  ``(1, block_size, Dh)`` blocks, and no gathered logical view or
  batch-wide score tensor ever exists in HBM;
- each step's partial scores (a ``Dh``-contraction — bitwise the same
  dot the one-shot einsum computes per column) land in a VMEM score-row
  scratch, the V block in a VMEM value scratch; the LAST page step
  masks by the slot's position and applies ONE exact softmax (the same
  max/exp/sum/divide chain ``jax.nn.softmax`` evaluates — written out
  explicitly because ``jax.nn.softmax`` carries a ``stop_gradient``
  Mosaic has no lowering for) before the single ``p @ V`` dot.

Decode's score row is ``O(T)`` per program (one query token), not the
``O(T²)`` of prefill attention, so the whole masked row fits VMEM and
the exact softmax — not an online-rescaling chain — is what keeps the
interpret-mode kernel BITWISE-identical to the XLA paged path on aligned
fp32 shapes (pinned in tests/test_pallas_decode.py): an online softmax
normalizes ``(p@v)/l`` where XLA computes ``(p/l)@v``, a rounding
difference the streaming buys nothing for at decode shapes.

Every BlockSpec in this file follows the TPU tiling rule (the last two
block dims must each be divisible by the dtype's native tile — (8, 128)
fp32, (16, 128) bf16, (32, 128) int8 — or equal the array dims): the
head-major pool makes each program's block ``(1, block_size, Dh)`` with
the singleton on a LEADING dim, quantized scale columns ride as
``[Hkv, M, 1]`` views (trailing singleton == array dim), and the
page/pos/seed/temperature/top-k vectors live in SMEM via scalar prefetch
where no tiling rule applies. The COMPILED kernel additionally needs
``block_size`` to be a multiple of 128: each page step stores its
partial scores at lane offset ``page·block_size`` of the score scratch,
and Mosaic only takes a dynamic lane offset it can prove 128-aligned
(block 16/32 is refused with "cannot statically prove that index in
dimension 1 is a multiple of 128"; 128 compiles — v5e, libtpu 0.0.34).
That is why ``serving.blocks.DEFAULT_BLOCK_SIZE`` is 128; other block
sizes still run interpreted. Nothing here probes or falls back: a
compiled kernel whose geometry is illegal or whose working set passes
the chip's VMEM raises (``policy.vmem_limit_bytes``), and the compiler's
own refusal propagates with its message. tests/test_aot_tpu_compile.py
compiles every serving kernel for a v5e with the real compiler,
devicelessly.

``fused_sample`` is the epilogue: greedy / temperature / top-k sampling
(``serving/sampling.sample_tokens`` semantics, per-slot runtime vectors)
as a Pallas kernel, one program per batch row, so the compiled decode
step emits ``[B] int32`` token ids with no full-vocab sort: the runtime-k
threshold is ``ops/topk.kth_largest``, a 32-step binary search over the
order-preserving integer image of the logits — the ONE definition that
``sample_tokens`` reads too, written within Mosaic's limits because this
kernel is the stricter of its two readers — and the categorical draw is
a Gumbel-max over hashed counter-based uniforms (``pltpu.prng`` is
TPU-only; the hash keeps the kernel interpretable on CPU). Greedy rows
and the kept top-k SET match ``sample_tokens`` exactly (the same
threshold, compared as a float in both, so ``-0.0`` and ``+0.0`` tie
here as they do there); the categorical draw itself matches in
distribution, not per-id (different RNG stream — the contract tests
assert the distribution, greedy ties, and membership).
Counting/argmax reductions run over exact small-integer fp32 images
(integer reductions have no Mosaic lowering; fp32 is exact below 2^24,
far above any vocab).

Dispatch resolves through the package-wide ``PADDLE_TPU_PALLAS`` policy
(``ops/pallas/policy.py``); only ``off`` selects the pure-XLA gather
path in ``transformer.decode_step_paged``.
"""

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops import topk as ops_topk
from paddle_tpu.ops.pallas import policy as _policy

NEG_INF = -1e30

# The pool layout this kernel generation is built for — the key prefix
# of the MEASURED_* tuning tables, so sweep entries taken on one layout
# are never consulted against another (a pre-relayout slot-major entry
# would otherwise advise tiles for a pool shape that no longer exists).
POOL_LAYOUT = "head_major"


def check_compiled_block_size(block_size: int, what: str):
    """A compiled (non-interpret) kernel's geometry gate: raises for a
    block size Mosaic refuses instead of letting the first compile die
    with a vector-store alignment message."""
    if int(block_size) % 128:
        raise ValueError(
            f"{what}: block_size {block_size} is not a multiple of "
            f"128 — the compiled kernel stores each page's scores at "
            f"lane offset page*block_size, which Mosaic only accepts "
            f"128-aligned. Use a block size of 128 (the engine "
            f"default), PADDLE_TPU_PALLAS=interpret, or =off.")


def _kv_store_dims(Dh: int, dtype, kv_dtype: str):
    """(stored last-dim, stored itemsize, dtype-key name) of the pool's
    KV arrays under a KV storage width: quantized pools store int8
    bytes (nibble-packed for int4) with the fp32 scale tables riding
    beside them."""
    if kv_dtype in (None, "none"):
        return Dh, jnp.dtype(dtype).itemsize, jnp.dtype(dtype).name
    if kv_dtype == "int4":
        return Dh // 2, 1, "int4"
    return Dh, 1, "int8"


# ---------------------------------------------------------------------------
# tile selection
# ---------------------------------------------------------------------------

# measured-best (block_size, pages-per-grid-step) keyed (POOL layout,
# span bucket, head_dim, dtype_name) — filled from on-chip sweeps
# (benchmarks/tune_flash_blocks.py --decode); consulted before the
# analytic default. The layout key guarantees entries swept on another
# pool layout are never consulted. The block_size entry is ADVISORY
# for engine configuration (the pool layout is the engine's choice);
# the kernel consults the tile only when the entry's block_size matches
# the pool it was actually handed. Span buckets are powers of two
# (lookup rounds up).
MEASURED_DECODE = {
    # (POOL_LAYOUT, span_bucket, head_dim, dtype): (block_size, tile)
}


def decode_vmem_bytes(M: int, P: int, block_size: int, G: int, Dh: int,
                      itemsize: int, kv_dtype: str = "none",
                      tile: int = 1) -> int:
    """Upper-bound VMEM residency of one (slot, kv-head) grid program
    at the head-major layout: the score-row and V scratch buffers
    spanning the slot's ``T = P·bs`` logical positions (scores counted
    twice — the softmax exp/normalize temporaries are row-sized), the
    q/out tiles, and the ``tile`` streamed K/V blocks in flight at
    their STORED width (double-buffered by the pipeline; quantized
    pools add the fp32 scale columns). The pool itself never sits in
    VMEM — scalar-prefetched placement streams only the mapped blocks —
    so the budget no longer scales with the pool size ``M``."""
    del M                        # streamed per-block, never resident
    T = P * int(block_size)
    if kv_dtype in (None, "none"):
        blk = int(block_size) * Dh * itemsize
    else:
        Dh_st = Dh // 2 if kv_dtype == "int4" else Dh
        blk = int(block_size) * (Dh_st + 4)      # values + scale col
    return (2 * G * T * 4                # score row + softmax temps
            + T * Dh * 4                 # V scratch
            + 2 * G * Dh * 4             # q, out
            + 4 * tile * blk)            # 2x tile in-flight K/V blocks


def select_decode_tile(P: int, block_size: int, head_dim: int,
                       dtype, kv_dtype: str = "none") -> int:
    """Pages streamed per grid step (each page is one scalar-prefetch-
    placed BlockSpec stream — ``tile`` of them run per step, amortizing
    grid overhead): the measured table first (when its advisory
    block_size matches the pool's), then the analytic default — the
    largest power-of-two divisor of P keeping the per-step stream at
    <= 256 rows (past that the extra in-flight blocks stop paying and
    VMEM pressure grows). Quantized pools key the measured table by
    their storage name ("int8"/"int4")."""
    span = P * int(block_size)
    bucket = 1 << max(0, (span - 1)).bit_length()     # next pow2 >= span
    _, _, name = _kv_store_dims(head_dim, dtype, kv_dtype)
    found = MEASURED_DECODE.get((POOL_LAYOUT, bucket, head_dim, name))
    if found and found[0] == block_size and P % found[1] == 0:
        return int(found[1])
    tile = 1
    while (tile * 2 <= P and P % (tile * 2) == 0
           and tile * 2 * block_size <= 256):
        tile *= 2
    return tile


# ---------------------------------------------------------------------------
# flash-decode attention kernel
# ---------------------------------------------------------------------------


def _widen_block(ref, scale_ref, kv_dtype):
    """One streamed pool block ``(1, bs, Dh-stored)`` widened to fp32
    ``[bs, Dh]`` in-register — the fused dequant. The op chain is
    EXACTLY the XLA quantized path's (``ops/q8.dequantize_kv``): exact
    integer unpack, astype(f32), broadcast row-scale multiply — so the
    kernel stays bitwise the XLA path whatever the storage width (the
    nibble unpack is all-integer shift arithmetic, bitwise on any
    backend)."""
    from paddle_tpu.ops import q8 as ops_q8
    rows = ref[0]
    if kv_dtype in (None, "none"):
        return rows.astype(jnp.float32)
    if kv_dtype == "int4":
        rows = ops_q8.unpack_int4(rows)
    return (rows.astype(jnp.float32)
            * scale_ref[0, :, 0][:, None])


def _decode_kernel(pages_ref, pos_ref, q_ref, *refs, block_size, P,
                   tile, G, Dh, scale, kv_dtype):
    """One (slot, kv-head, page-step) program. ``pages``/``pos`` are
    scalar-prefetched (SMEM); q/o blocks are ``(1, 1, G, Dh)``; each of
    the ``tile`` K/V streams is a ``(1, bs, Dh-stored)`` pool block
    placed through ``pages[b, j·tile + t]`` (+ a ``(1, bs, 1)`` scale
    column per stream for quantized pools). Page step ``j`` writes its
    partial scores (a Dh-contraction, bitwise the one-shot einsum's
    columns) and fp32-widened V rows into VMEM scratch at the logical
    offset; the LAST step masks by the slot's position and mirrors the
    XLA gather path's op chain exactly (divide-by-sqrt(Dh), -1e30 mask,
    max/exp/sum/divide softmax) so aligned fp32 shapes — and quantized
    pools, whose dequant chain is elementwise-identical — reproduce its
    logits bitwise."""
    quant = kv_dtype not in (None, "none")
    krefs = refs[:tile]
    vrefs = refs[tile:2 * tile]
    n_in = 2 * tile + (2 * tile if quant else 0)
    if quant:
        ksrefs = refs[2 * tile:3 * tile]
        vsrefs = refs[3 * tile:4 * tile]
    else:
        ksrefs = vsrefs = (None,) * tile
    o_ref, s_scr, v_scr = refs[n_in], refs[n_in + 1], refs[n_in + 2]
    b = pl.program_id(0)
    j = pl.program_id(2)
    bs = int(block_size)
    T = P * bs
    q = q_ref[0, 0].astype(jnp.float32)                  # [G, Dh]
    for t in range(tile):           # static unroll: tile pages/step
        ks = _widen_block(krefs[t], ksrefs[t], kv_dtype)
        vs = _widen_block(vrefs[t], vsrefs[t], kv_dtype)
        s = jax.lax.dot_general(q, ks, (((1,), (1,)), ((), ())))
        off = (j * tile + t) * bs
        s_scr[:, pl.ds(off, bs)] = s
        v_scr[pl.ds(off, bs), :] = vs

    @pl.when(j == P // tile - 1)
    def _finish():
        s = s_scr[...] / scale
        valid = (jax.lax.broadcasted_iota(jnp.int32, (G, T), 1)
                 <= pos_ref[b])                          # logical mask
        s = jnp.where(valid, s, NEG_INF)
        # jax.nn.softmax's exact chain, written out (its stop_gradient
        # has no Mosaic lowering; numerically it is the identity)
        m = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.exp(s - m)
        p = e / jnp.sum(e, axis=-1, keepdims=True)
        o_ref[0, 0] = jax.lax.dot_general(
            p, v_scr[...], (((1,), (0,)), ((), ())))


def flash_decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                           pages: jax.Array, pos: jax.Array, *,
                           block_size: int,
                           tile: Optional[int] = None,
                           k_scale: Optional[jax.Array] = None,
                           v_scale: Optional[jax.Array] = None,
                           kv_dtype: str = "none",
                           interpret: bool = False) -> jax.Array:
    """One decode step's attention straight off the head-major paged
    pool.

    q [B, Hkv, G, Dh] (grouped-query layout, G = n_heads/kv_heads),
    k/v the flat pool [Hkv, M, Dh], pages [B, P] int32 physical block
    ids, pos [B] int32 per-slot positions → fp32 [B, Hkv, G, Dh]. The
    caller owns the pool WRITE of the step's new k/v (a cheap scatter)
    and must perform it before this reads — position ``pos[b]`` attends
    to itself.

    Quantized pools (``kv_dtype`` "int8"/"int4") pass the int8 value
    arrays ([Hkv, M, Dh] or nibble-packed [Hkv, M, Dh//2]) plus the
    per-(head, position) fp32 scale tables ``k_scale``/``v_scale``
    [Hkv, M]: blocks stream into VMEM at their stored width and the
    dequant multiply runs in-register — history crosses HBM at 1 (int8)
    or 1/2 (int4) byte/elt.

    Grid (slot, kv-head, page-step) with ``pages``/``pos`` scalar-
    prefetched. Compiled (``interpret=False``) the block size must be
    a multiple of 128 and the per-program working set
    (``decode_vmem_bytes``) must fit the target chip's VMEM — both
    raise here, at trace time, rather than degrade."""
    B, Hkv, G, Dh = q.shape             # Dh is always the LOGICAL dim
    quant = kv_dtype not in (None, "none")
    M = k.shape[1]
    P = pages.shape[1]
    bs = int(block_size)
    if quant and (k_scale is None or v_scale is None):
        raise ValueError(f"kv_dtype={kv_dtype} needs k_scale/v_scale")
    if tile is None:
        tile = select_decode_tile(P, bs, Dh, k.dtype, kv_dtype)
    if P % tile:
        raise ValueError(f"flash_decode: tile {tile} must divide the "
                         f"page-vector length {P}")
    tile = int(tile)
    Dh_st = k.shape[-1]                 # stored last dim (packed int4)
    T = P * bs
    if not interpret:
        check_compiled_block_size(bs, "flash_decode_attention")
    params = _policy.compiled_kernel_params(
        interpret, decode_vmem_bytes(M, P, bs, G, Dh, k.dtype.itemsize,
                                     kv_dtype, tile=tile),
        "flash_decode_attention")
    kernel = functools.partial(
        _decode_kernel, block_size=bs, P=P, tile=tile, G=G, Dh=Dh,
        scale=math.sqrt(Dh), kv_dtype=kv_dtype if quant else "none")

    def kv_spec(t):
        return pl.BlockSpec(
            (1, bs, Dh_st),
            lambda b, h, j, pg, ps, t=t: (h, pg[b, j * tile + t], 0))

    def sc_spec(t):
        return pl.BlockSpec(
            (1, bs, 1),
            lambda b, h, j, pg, ps, t=t: (h, pg[b, j * tile + t], 0))

    in_specs = ([pl.BlockSpec((1, 1, G, Dh),
                              lambda b, h, j, pg, ps: (b, h, 0, 0))]
                + [kv_spec(t) for t in range(tile)] * 2)
    args = [q] + [k] * tile + [v] * tile
    if quant:
        in_specs += [sc_spec(t) for t in range(tile)] * 2
        args += ([k_scale.reshape(Hkv, M, 1)] * tile
                 + [v_scale.reshape(Hkv, M, 1)] * tile)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, P // tile),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, G, Dh),
                               lambda b, h, j, pg, ps: (b, h, 0, 0)),
        scratch_shapes=[pltpu.VMEM((G, T), jnp.float32),
                        pltpu.VMEM((T, Dh), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, Dh), jnp.float32),
        interpret=interpret,
        **params,
    )(pages.astype(jnp.int32), jnp.asarray(pos, jnp.int32).reshape(B),
      *args)


# ---------------------------------------------------------------------------
# fused sampling epilogue
# ---------------------------------------------------------------------------


def _hash_uniform(seed: jax.Array, row: jax.Array,
                  shape: Tuple[int, ...]) -> jax.Array:
    """Counter-based uniforms in (0, 1): a splitmix-style integer hash
    of (seed, row, lane) — deterministic for a given seed, independent
    across rows and lanes, and pure jnp (runs under interpret and
    Mosaic alike, unlike the TPU-only pltpu PRNG)."""
    lane = jax.lax.broadcasted_iota(jnp.uint32, shape, len(shape) - 1)
    h = (seed.astype(jnp.uint32)
         + row.astype(jnp.uint32) * jnp.uint32(0x9E3779B9)
         + (lane + jnp.uint32(1)) * jnp.uint32(0x85EBCA6B))
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x7FEB352D)
    h = h ^ (h >> 15)
    h = h * jnp.uint32(0x846CA68B)
    h = h ^ (h >> 16)
    # 24 random bits as fp32: Mosaic has no uint32 -> float32 cast, and
    # h >> 8 < 2^24 is the same number read as int32
    bits = jax.lax.bitcast_convert_type(h >> 8, jnp.int32)
    return (bits.astype(jnp.float32) + 0.5) * (1.0 / (1 << 24))


def _first_argmax(x: jax.Array, iota: jax.Array) -> jax.Array:
    """First-index argmax over the last axis ([1, V] -> scalar) — the
    ``jnp.argmax`` tie convention, written as max+where+min because
    ``lax.argmax`` has no Mosaic lowering. ``iota`` is the fp32 lane
    index (exact below 2^24; integer min-reductions don't lower)."""
    m = jnp.max(x, axis=-1, keepdims=True)
    V = x.shape[-1]
    return jnp.min(jnp.where(x == m, iota, float(V))).astype(jnp.int32)


def _sample_kernel(seed_ref, temp_ref, topk_ref, logits_ref, o_ref):
    """One batch row: greedy argmax, the top-k threshold by selection
    (``ops/topk.kth_largest`` on the ``[1, V]`` block), temperature
    scale, Gumbel-max categorical — ``sample_tokens`` semantics with no
    full-vocab sort and no second dispatch. The per-row controls are
    scalar-prefetched (SMEM); logits ride as a ``(1, 1, V)`` block of
    the ``[B, 1, V]`` view (tiling-legal: the trailing two block dims
    equal the array dims)."""
    row = pl.program_id(0)
    v = logits_ref[0, 0].astype(jnp.float32)[None, :]     # [1, V]
    V = v.shape[-1]
    # Mosaic's iota is integer-only; the fp32 image is exact below 2^24
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, V), 1).astype(
        jnp.float32)
    greedy = _first_argmax(v, iota)
    k = jnp.clip(topk_ref[row], 0, V)
    kth = ops_topk.kth_largest(v, jnp.maximum(k, 1))      # [1, 1]
    keep = (k <= 0) | (v >= kth)          # ties at the threshold survive
    z = jnp.where(keep, v, -jnp.inf)
    temp = temp_ref[row]
    z = z / jnp.where(temp > 0, temp, 1.0)
    g = -jnp.log(-jnp.log(_hash_uniform(seed_ref[0], row, (1, V))))
    sampled = _first_argmax(z + g, iota)
    pick = jnp.where(temp > 0, sampled, greedy).astype(jnp.int32)
    o_ref[...] = jnp.reshape(pick, (1, 1, 1))


def fused_sample(logits: jax.Array, seed: jax.Array,
                 temperature: jax.Array, top_k: jax.Array, *,
                 interpret: bool = False) -> jax.Array:
    """Sampling epilogue kernel: logits [B, V] fp32, scalar int32
    ``seed``, per-slot runtime ``temperature`` [B] / ``top_k`` [B] →
    sampled ids [B] int32. Greedy rows (temperature <= 0) and the kept
    top-k set match ``serving/sampling.sample_tokens`` exactly; the
    categorical draw matches in distribution (hash-Gumbel stream, not
    jax.random's). Seed/temperature/top-k ride as scalar prefetch, so
    the only tiled operand is the logits view ``[B, 1, V]``."""
    B, V = logits.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, 1, V),
                               lambda b, sd, tp, tk: (b, 0, 0))],
        out_specs=pl.BlockSpec((1, 1, 1),
                               lambda b, sd, tp, tk: (b, 0, 0)),
    )
    out = pl.pallas_call(
        _sample_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, 1), jnp.int32),
        interpret=interpret,
    )(jnp.reshape(jnp.asarray(seed, jnp.int32), (1,)),
      jnp.asarray(temperature, jnp.float32).reshape(B),
      jnp.asarray(top_k, jnp.int32).reshape(B),
      logits.reshape(B, 1, V))
    return out[:, 0, 0]


def fused_spec_verify(logits: jax.Array, draft: jax.Array,
                      seed: jax.Array, temperature: jax.Array,
                      top_k: jax.Array, valid: jax.Array, *,
                      interpret: bool = False):
    """Speculative-decoding accept/reject epilogue: the PR-9
    ``fused_sample`` kernel run once per VERIFY-WINDOW row (logits
    [B, W, V] flattened to [B·W, V] — per-slot temperature/top_k
    broadcast over the window) followed by the accept fold
    (``serving.sampling.spec_accept``: leading draft-match run + one
    correction/bonus token, capped to ``valid`` rows). Greedy rows are
    the kernel's exact first-index argmax, so the fused path emits
    bitwise the ``spec_verify_tokens`` greedy tokens — the spec
    engine's bitwise-greedy contract holds on either epilogue.
    Returns (sampled [B, W] int32, n_emitted [B] int32)."""
    from paddle_tpu.serving import sampling as _sampling
    B, W, V = logits.shape
    sampled = fused_sample(
        logits.reshape(B * W, V), seed,
        jnp.repeat(temperature, W), jnp.repeat(top_k, W),
        interpret=interpret).reshape(B, W)
    return sampled, _sampling.spec_accept(sampled, draft, valid)
