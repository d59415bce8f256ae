"""Chunked-prefill Pallas kernels over the head-major paged KV pool.

The cold-prefill half of TTFT is one ``transformer.prefill_into_blocks``
call per chunk: under XLA each layer gathers the context out of the pool
into an HBM ``[S, Hkv, Dh]`` view, concatenates the chunk's fresh K/V,
and keeps a ``[C, H, S+C]`` score tensor in HBM between the softmax
stages; the chunk's KV then lands in the pool as compiler-emitted
masked-span writes (the exact pattern CUDA-L2 in PAPERS.md shows
library-emitted kernels leave margin on). Two hand-scheduled kernels
replace that, behind the same ``PADDLE_TPU_PALLAS`` knob as the decode
kernels — both built for the head-major pool ``[Hkv, M, Dh]`` under
the TPU tiling rule, and both raising (never degrading to XLA) when
compiled at a geometry the chip cannot take; see ops/pallas/decode.py
for the rule, the 128-multiple block size the compiled score-scratch
stores need, and the VMEM policy:

- :func:`flash_chunk_prefill` — one chunk's attention against its
  context, straight off the pool: grid ``(kv-head, ctx-page-step)``
  with the slot's context pages **scalar-prefetched**, so each step's
  ``(1, block_size, Dh)`` context block is PLACED by the page table
  (only MAPPED blocks ever stream; for quantized pools the dequant
  multiply fuses into the stream, so history crosses HBM at its stored
  1 or 1/2 byte/elt). Partial scores (Dh-contractions, bitwise the
  one-shot einsum's columns) accumulate into a VMEM score scratch; the
  LAST step appends the chunk's own K/V and applies ONE exact softmax
  under the context-visible + chunk-causal mask. No gathered context
  view and no score tensor ever exist in HBM. Exact softmax (not
  online rescaling) for the same reason as ``flash_decode_attention``:
  it reproduces the XLA path's op chain, so the interpret-mode
  kernel is BITWISE the XLA path on aligned fp32 shapes (pinned in
  tests/test_pallas_prefill.py).

- :func:`paged_span_write` — the chunk's masked span writes: grid over
  (layer, chunk page), each program's output block mapped THROUGH the
  scalar-prefetched page vector, pool buffers aliased in-place. Padded
  rows keep the span's old bytes (the RMW the XLA path expresses
  as slice + where + update-slice), and quantized pools write values
  and scale rows through the same kernel (scale tables ride as
  trailing-singleton ``[L, Hkv, M, 1]`` views — tiling-legal). One
  program holds ONE layer's ``(1, Hkv, bs, Dh)`` span: a block over
  all layers overflowed VMEM at block size 128 (RESOURCE_EXHAUSTED on
  v5e, libtpu 0.0.34).

Tiling: ``tile`` context pages stream per grid step (each its own
scalar-prefetch-placed BlockSpec) — measured winners from
``benchmarks/tune_flash_blocks.py --prefill`` go in ``MEASURED_PREFILL``
(keyed by POOL LAYOUT first, so entries swept on another layout are
never consulted; the block-size entry stays an engine-configuration
hint, consulted only when it matches the pool actually handed over);
the analytic default mirrors the decode kernel's.
"""

import functools
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas import policy as _policy
from paddle_tpu.ops.pallas.decode import (NEG_INF, POOL_LAYOUT,
                                          _kv_store_dims, _widen_block,
                                          check_compiled_block_size)

# measured-best (block_size, ctx pages-per-grid-step) keyed (POOL
# layout, context-span bucket, chunk bucket, head_dim, dtype_name) —
# filled from on-chip sweeps (benchmarks/tune_flash_blocks.py
# --prefill); consulted before the analytic default. Advisory semantics
# match MEASURED_DECODE: the block_size entry is a hint for engine
# configuration, and the tile is used only when that advisory matches
# the pool the kernel was handed.
MEASURED_PREFILL = {
    # (POOL_LAYOUT, span_bucket, chunk_bucket, head_dim, dtype):
    #     (block_size, tile)
}


def prefill_vmem_bytes(M: int, S: int, C: int, G: int, Dh: int,
                       itemsize: int, kv_dtype: str = "none",
                       stream_rows: Optional[int] = None) -> int:
    """Upper-bound VMEM residency of one kv-head grid program at the
    head-major layout: the ``[C·G, S+C]`` score scratch (counted twice
    — softmax temporaries are score-sized), the fp32 V scratch over
    context + chunk, the chunk K/V and q/out tiles, and the streamed
    context blocks in flight at their stored width (double-buffered;
    ``stream_rows`` is the per-step stream, ``tile·block_size`` when
    the caller knows its tile — the analytic selector caps it at 256
    rows, the default charged here, but a MEASURED_PREFILL winner may
    exceed it; quantized pools add the fp32 scale columns). The pool
    itself never sits in VMEM — scalar-prefetched placement streams
    only the mapped blocks, so the budget no longer scales with the
    pool size ``M``."""
    del M                        # streamed per-block, never resident
    T = S + C
    if kv_dtype in (None, "none"):
        blk_row = Dh * itemsize
    else:
        Dh_st = Dh // 2 if kv_dtype == "int4" else Dh
        blk_row = Dh_st + 4                  # values + scale col
    if stream_rows is None:
        stream_rows = min(max(S, 1), 256)
    stream = 4 * stream_rows * blk_row
    return (2 * C * G * T * 4            # scores + softmax temps
            + T * Dh * 4                 # fp32 V scratch
            + 2 * C * Dh * 4             # chunk k/v tiles
            + 2 * C * G * Dh * 4         # q, out
            + stream)                    # in-flight context blocks


def select_prefill_tile(P_ctx: int, block_size: int, chunk: int,
                        head_dim: int, dtype,
                        kv_dtype: str = "none") -> int:
    """Context pages streamed per grid step: the measured table first
    (when its advisory block_size matches the pool's), then the
    analytic default — largest power-of-two divisor of ``P_ctx``
    keeping the per-step stream at <= 256 rows."""
    if P_ctx < 1:
        return 1
    span = P_ctx * int(block_size)
    sb = 1 << max(0, (span - 1)).bit_length()
    cb = 1 << max(0, (int(chunk) - 1)).bit_length()
    _, _, name = _kv_store_dims(head_dim, dtype, kv_dtype)
    found = MEASURED_PREFILL.get((POOL_LAYOUT, sb, cb, head_dim, name))
    if found and found[0] == block_size and P_ctx % found[1] == 0:
        return int(found[1])
    tile = 1
    while (tile * 2 <= P_ctx and P_ctx % (tile * 2) == 0
           and tile * 2 * block_size <= 256):
        tile *= 2
    return tile


# ---------------------------------------------------------------------------
# chunk attention kernel
# ---------------------------------------------------------------------------


def _chunk_kernel(pages_ref, *refs, block_size, P_ctx, tile, C, G, Dh,
                  scale, kv_dtype):
    """One (kv-head, ctx-page-step) program. The context pages are
    scalar-prefetched; blocks are q ``(C, 1, G, Dh)``, chunk k/v
    ``(1, C, Dh)`` (head-major), and per stream one ``(1, bs, Dh-
    stored)`` pool block (+ ``(1, bs, 1)`` scale column when
    quantized). Page step ``j`` writes its partial scores and widened V
    rows into scratch at the logical offset; the LAST step appends the
    chunk's own K/V behind the context and mirrors the XLA chunk
    path's op chain exactly (context fully visible, chunk causal,
    -1e30 mask, max/exp/sum/divide softmax) for the bitwise contract.
    All dots are 2D (``[C·G, ·]``) — Mosaic's dot only takes rank-2 —
    which cannot move a single bit: each score/output element is the
    same length-Dh / length-T contraction either way."""
    quant = kv_dtype not in (None, "none")
    krefs = refs[:tile]
    vrefs = refs[tile:2 * tile]
    off = 2 * tile
    if quant:
        ksrefs = refs[off:off + tile]
        vsrefs = refs[off + tile:off + 2 * tile]
        off += 2 * tile
    else:
        ksrefs = vsrefs = (None,) * tile
    q_ref, kck_ref, vck_ref = refs[off], refs[off + 1], refs[off + 2]
    o_ref, s_scr, v_scr = refs[off + 3], refs[off + 4], refs[off + 5]
    j = pl.program_id(1)
    bs = int(block_size)
    S = P_ctx * bs
    T = S + C
    q = q_ref[:, 0].astype(jnp.float32).reshape(C * G, Dh)
    for t in range(tile):           # static unroll: tile pages/step
        ks = _widen_block(krefs[t], ksrefs[t], kv_dtype)
        vs = _widen_block(vrefs[t], vsrefs[t], kv_dtype)
        s = jax.lax.dot_general(q, ks, (((1,), (1,)), ((), ())))
        o = (j * tile + t) * bs
        s_scr[:, pl.ds(o, bs)] = s
        v_scr[pl.ds(o, bs), :] = vs

    @pl.when(j == P_ctx // tile - 1)
    def _finish():
        kck = kck_ref[0].astype(jnp.float32)             # [C, Dh]
        vck = vck_ref[0].astype(jnp.float32)
        s2 = jax.lax.dot_general(q, kck, (((1,), (1,)), ((), ())))
        s_scr[:, pl.ds(S, C)] = s2
        v_scr[pl.ds(S, C), :] = vck
        s = s_scr[...] / scale
        # context fully visible, chunk causally masked: position t is
        # visible to chunk row c iff t <= S + c (row r of the [C·G, T]
        # image belongs to chunk row r // G)
        row = jax.lax.broadcasted_iota(jnp.int32, (C * G, T), 0) // G
        col = jax.lax.broadcasted_iota(jnp.int32, (C * G, T), 1)
        s = jnp.where(col <= S + row, s, NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.exp(s - m)
        p = e / jnp.sum(e, axis=-1, keepdims=True)
        out = jax.lax.dot_general(p, v_scr[...],
                                  (((1,), (0,)), ((), ())))
        o_ref[...] = out.reshape(C, 1, G, Dh)


def _cold_chunk_kernel(q_ref, kck_ref, vck_ref, o_ref, *, C, G, Dh,
                       scale):
    """A cold first chunk (no context): pure chunk-causal attention in
    registers — no pool inputs, no scratch, same op chain."""
    q = q_ref[:, 0].astype(jnp.float32).reshape(C * G, Dh)
    kck = kck_ref[0].astype(jnp.float32)
    vck = vck_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, kck, (((1,), (1,)), ((), ()))) / scale
    row = jax.lax.broadcasted_iota(jnp.int32, (C * G, C), 0) // G
    col = jax.lax.broadcasted_iota(jnp.int32, (C * G, C), 1)
    s = jnp.where(col <= row, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    p = e / jnp.sum(e, axis=-1, keepdims=True)
    out = jax.lax.dot_general(p, vck, (((1,), (0,)), ((), ())))
    o_ref[...] = out.reshape(C, 1, G, Dh)


def flash_chunk_prefill(q: jax.Array, k_chunk: jax.Array,
                        v_chunk: jax.Array, k: jax.Array, v: jax.Array,
                        pages: jax.Array, *, block_size: int,
                        tile: Optional[int] = None,
                        k_scale: Optional[jax.Array] = None,
                        v_scale: Optional[jax.Array] = None,
                        kv_dtype: str = "none",
                        interpret: bool = False) -> jax.Array:
    """One prefill chunk's attention against its pool-resident context.

    q [C, Hkv, G, Dh] (grouped-query layout), k_chunk/v_chunk
    [C, Hkv, Dh] the chunk's OWN fresh K/V (exact, pre-quantization —
    in-chunk attention reads what the forward computed; only the pool
    write is rounded), k/v the head-major flat pool [Hkv, M, Dh-stored],
    pages [P_ctx] int32 the slot's context pages (context length S =
    P_ctx·block_size is static, like the XLA chunk path's span
    specialization) → fp32 [C, Hkv, G, Dh]. Quantized pools also pass
    ``k_scale``/``v_scale`` [Hkv, M] and the matching ``kv_dtype``.

    A cold first chunk (P_ctx = 0) skips the pool inputs entirely —
    the kernel is then pure chunk-causal attention. Contextful chunks
    run grid (kv-head, ctx-page-step) with the pages scalar-prefetched
    and each step's context block placed through the page table."""
    C, Hkv, G, Dh = q.shape
    quant = kv_dtype not in (None, "none")
    P_ctx = int(pages.shape[0])
    bs = int(block_size)
    if quant and (k_scale is None or v_scale is None):
        raise ValueError(f"kv_dtype={kv_dtype} needs k_scale/v_scale")
    if tile is None:
        tile = select_prefill_tile(P_ctx, bs, C, Dh, k.dtype, kv_dtype)
    if P_ctx and P_ctx % tile:
        raise ValueError(f"flash_chunk_prefill: tile {tile} must "
                         f"divide the context page count {P_ctx}")
    tile = int(tile)
    # chunk K/V ride head-major too: the (1, C, Dh) block keeps the
    # tiling-legal trailing dims (the [C, Hkv, Dh] layout would put the
    # head singleton second-to-last)
    kck = jnp.swapaxes(k_chunk, 0, 1)
    vck = jnp.swapaxes(v_chunk, 0, 1)
    if P_ctx and not interpret:
        check_compiled_block_size(bs, "flash_chunk_prefill")
    params = _policy.compiled_kernel_params(
        interpret, prefill_vmem_bytes(0, P_ctx * bs, C, G, Dh,
                                      k.dtype.itemsize, kv_dtype,
                                      stream_rows=tile * bs),
        "flash_chunk_prefill")
    if not P_ctx:
        kernel = functools.partial(_cold_chunk_kernel, C=C, G=G, Dh=Dh,
                                   scale=math.sqrt(Dh))
        return pl.pallas_call(
            kernel,
            grid=(Hkv,),
            in_specs=[
                pl.BlockSpec((C, 1, G, Dh), lambda h: (0, h, 0, 0)),
                pl.BlockSpec((1, C, Dh), lambda h: (h, 0, 0)),
                pl.BlockSpec((1, C, Dh), lambda h: (h, 0, 0)),
            ],
            out_specs=pl.BlockSpec((C, 1, G, Dh),
                                   lambda h: (0, h, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((C, Hkv, G, Dh),
                                           jnp.float32),
            interpret=interpret,
            **params,
        )(q, kck, vck)
    M = k.shape[1]
    Dh_st = k.shape[-1]                 # stored last dim (packed int4)
    S = P_ctx * bs
    kernel = functools.partial(
        _chunk_kernel, block_size=bs, P_ctx=P_ctx, tile=tile, C=C,
        G=G, Dh=Dh, scale=math.sqrt(Dh),
        kv_dtype=kv_dtype if quant else "none")

    def kv_spec(t):
        return pl.BlockSpec(
            (1, bs, Dh_st),
            lambda h, j, pg, t=t: (h, pg[j * tile + t], 0))

    def sc_spec(t):
        return pl.BlockSpec(
            (1, bs, 1),
            lambda h, j, pg, t=t: (h, pg[j * tile + t], 0))

    in_specs = [kv_spec(t) for t in range(tile)] * 2
    args = [k] * tile + [v] * tile
    if quant:
        in_specs += [sc_spec(t) for t in range(tile)] * 2
        args += ([k_scale.reshape(Hkv, M, 1)] * tile
                 + [v_scale.reshape(Hkv, M, 1)] * tile)
    in_specs += [
        pl.BlockSpec((C, 1, G, Dh), lambda h, j, pg: (0, h, 0, 0)),
        pl.BlockSpec((1, C, Dh), lambda h, j, pg: (h, 0, 0)),
        pl.BlockSpec((1, C, Dh), lambda h, j, pg: (h, 0, 0)),
    ]
    args += [q, kck, vck]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Hkv, P_ctx // tile),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((C, 1, G, Dh),
                               lambda h, j, pg: (0, h, 0, 0)),
        scratch_shapes=[pltpu.VMEM((C * G, S + C), jnp.float32),
                        pltpu.VMEM((S + C, Dh), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((C, Hkv, G, Dh), jnp.float32),
        interpret=interpret,
        **params,
    )(pages.astype(jnp.int32), *args)


# ---------------------------------------------------------------------------
# masked span-write kernel
# ---------------------------------------------------------------------------


def _span_write_kernel(n: int):
    """Kernel over ``n`` (span, pool) array pairs: one grid program per
    (layer, chunk page), output blocks mapped through the scalar-
    prefetched page vector, pool buffers aliased — so each program
    touches exactly one layer's ``block_size``-token span per array.
    Padded rows (mask 0) keep the pool's old bytes, read from the pool
    INPUT block: aliasing makes input and output one HBM buffer, but
    only an input block is copied into VMEM before the body runs — on
    the chip the output block starts as whatever VMEM held (the first
    v5e run wrote that garbage over every padded row and decode read
    NaN out of it; the interpreter pre-fills outputs and hid it)."""

    def kernel(pages_ref, mask_ref, *refs):
        spans, pools, outs = refs[:n], refs[n:2 * n], refs[2 * n:]
        m = mask_ref[0, :, 0] != 0                        # [bs]
        for s_ref, p_ref, o_ref in zip(spans, pools, outs):
            mv = m.reshape((1, 1, -1) + (1,) * (o_ref.ndim - 3))
            o_ref[...] = jnp.where(mv, s_ref[...], p_ref[...])

    return kernel


def _tiled_bytes(shape, dtype) -> int:
    """Bytes a VMEM block occupies once its trailing two dims are
    padded to the dtype's native tile (lanes to 128, sublanes to
    8/16/32 for 4/2/1-byte elements)."""
    itemsize = jnp.dtype(dtype).itemsize
    sub = 8 * max(1, 4 // itemsize)
    lead = math.prod(shape[:-2])
    return (lead * -(-shape[-2] // sub) * sub
            * -(-shape[-1] // 128) * 128 * itemsize)


def span_write_vmem_bytes(blocks) -> int:
    """Upper-bound VMEM residency of one (layer, page) span-write
    program over ``blocks`` = [(block shape, dtype), ...]: per array a
    span block, the aliased pool block in and out, each double-
    buffered by the pipeline."""
    return sum(6 * _tiled_bytes(shape, dtype) for shape, dtype in blocks)


def paged_span_write(pool: Dict[str, jax.Array],
                     spans: Dict[str, jax.Array],
                     pages: jax.Array, valid: jax.Array, *,
                     block_size: int,
                     interpret: bool = False) -> Dict[str, jax.Array]:
    """Write one chunk's spans into its pool pages, masked per row.

    ``pool`` maps array names to head-major pool buffers
    [L, Hkv, M, ...]; ``spans`` maps the SAME names to the chunk's
    stacked spans [L, Hkv, pc·bs, ...] (values and, for quantized
    pools, scale rows alike — scale tables are the 3D [L, Hkv, M] /
    [L, Hkv, pc·bs] case and ride as trailing-singleton 4D views);
    ``pages`` [pc] int32 the chunk's physical pages; ``valid`` [pc·bs]
    bool the per-row write mask (False rows keep the pool's old bytes —
    the RMW equivalent of the decode scatter's mode="drop"). Returns
    the updated pool arrays.

    Grid (L, pc); each program's blocks are one layer's one-page span
    per array, placed by indexing the output BlockSpec through the
    scalar-prefetched page vector — the hand-scheduled form of the
    masked contiguous-span writes XLA emits on its own path, with the
    pool aliased in-place instead of round-tripping a pool-sized copy.
    Every block keeps its trailing two dims tiling-legal: the page
    axis sits third-from-last (``(1, Hkv, bs, Dh)`` value blocks,
    ``(1, Hkv, bs, 1)`` scale blocks, ``(1, bs, 1)`` mask blocks), and
    the per-program VMEM no longer grows with the layer count."""
    names = sorted(spans)
    bs = int(block_size)
    pc = int(pages.shape[0])
    n = len(names)
    mask = valid.astype(jnp.int32).reshape(pc, bs, 1)
    # 3D arrays (the scale tables) ride as trailing-singleton 4D views
    # so their blocks end in (bs, 1) — legal under the tiling rule
    three_d = {nm for nm in names if pool[nm].ndim == 3}

    def view(a):
        return a[..., None] if a.ndim == 3 else a

    pools4 = {nm: view(pool[nm]) for nm in names}
    spans4 = {nm: view(spans[nm]) for nm in names}
    L = pools4[names[0]].shape[0]

    def block(a):
        return (1, a.shape[1], bs) + a.shape[3:]

    def span_spec(a):
        nd = a.ndim

        def imap(l, j, pg, nd=nd):
            return (l, 0, j) + (0,) * (nd - 3)

        return pl.BlockSpec(block(a), imap)

    def pool_spec(a):
        nd = a.ndim

        def imap(l, j, pg, nd=nd):
            return (l, 0, pg[j]) + (0,) * (nd - 3)

        return pl.BlockSpec(block(a), imap)

    params = _policy.compiled_kernel_params(
        interpret, span_write_vmem_bytes(
            [(block(pools4[nm]), pools4[nm].dtype) for nm in names]),
        "paged_span_write")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(L, pc),
        in_specs=([pl.BlockSpec((1, bs, 1), lambda l, j, pg: (j, 0, 0))]
                  + [span_spec(spans4[nm]) for nm in names]
                  + [pool_spec(pools4[nm]) for nm in names]),
        out_specs=[pool_spec(pools4[nm]) for nm in names],
    )
    outs = pl.pallas_call(
        _span_write_kernel(n),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(pools4[nm].shape,
                                        pools4[nm].dtype)
                   for nm in names],
        # pool inputs alias the outputs: scalar-prefetch pages ride
        # first, then the mask, the spans, and the pool buffers at
        # kernel-arg indices 1..; the alias indices COUNT the scalar-
        # prefetch operand, matching pallas_call's flat operand order
        input_output_aliases={2 + n + i: i for i in range(n)},
        interpret=interpret,
        **params,
    )(pages.astype(jnp.int32), mask,
      *[spans4[nm] for nm in names], *[pools4[nm] for nm in names])
    return {nm: (o[..., 0] if nm in three_d else o)
            for nm, o in zip(names, outs)}
