#!/usr/bin/env python
"""Sweep Pallas kernel tilings on the real chip.

Default mode sweeps flash-ATTENTION block sizes and prints the best
(block_q, block_k) per (seq, head_dim, dtype) — paste winners into
ops/pallas/attention.py MEASURED_BLOCKS.

``--decode`` sweeps the flash-DECODE kernel over (KV block size — the
pool's M-tile, i.e. each grid program's ``(1, block_size, Dh)`` block
— x pages-per-grid-step tile) per (span, head_dim, dtype) on
HEAD-MAJOR ``[Hkv, M, Dh]`` pools — paste winners into
ops/pallas/decode.py MEASURED_DECODE (keys carry the POOL_LAYOUT
token, so entries swept on another layout are never consulted). The
block-size axis is advisory for ENGINE configuration (the pool layout
is the engine's choice); the tile axis is the kernel's streaming
granularity, consulted at dispatch when the advisory block size
matches the pool actually handed over (analytic VMEM-budget default
otherwise).

``--prefill`` sweeps the chunked-PREFILL kernel
(``ops.pallas.prefill.flash_chunk_prefill``) over (chunk tokens x
block size x ctx pages-per-step tile) per (context span, head_dim,
dtype) — paste winners into ops/pallas/prefill.py MEASURED_PREFILL
(layout-keyed the same way). Same advisory-only selection semantics
as --decode. ``--dtypes`` may name the quantized pool storages
``int8``/``int4`` to sweep the fused-dequant gather.

Usage: python benchmarks/tune_flash_blocks.py [--seqs 2048,8192]
       [--head-dims 64,128] [--dtypes bfloat16,float32] [--iters 20]
       [--decode | --prefill] [--chunks 64,128] [--slots 8]
       [--kv-heads 8] [--q-per-kv 1] [--interpret]
"""

import argparse
import itertools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def attention_sweep(args):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.pallas import attention as fa
    from paddle_tpu.utils.sync import host_sync

    candidates = [(64, 64), (64, 128), (128, 64), (128, 128),
                  (128, 256), (256, 128), (256, 256), (128, 512)]
    budget = fa.planning_budget(interpret=False)
    rng = np.random.RandomState(0)
    results = {}
    for seq, d, dname in itertools.product(
            (int(s) for s in args.seqs.split(",")),
            (int(s) for s in args.head_dims.split(",")),
            args.dtypes.split(",")):
        dtype = jnp.dtype(dname)
        bh = args.batch_heads
        q = jnp.asarray(rng.randn(1, seq, bh, d), dtype)
        best = None
        for bq, bk in candidates:
            bq_c, bk_c = min(bq, seq), min(bk, seq)
            tp = fa._pad_to_blocks(seq, bq_c, bk_c)
            if fa._vmem_working_set(tp, d, bq_c, bk_c,
                                    dtype.itemsize) > budget:
                continue
            try:
                f = jax.jit(lambda q_: fa.flash_attention(
                    q_, q_, q_, causal=True, block_q=bq_c, block_k=bk_c))
                host_sync(f(q))                      # compile + smoke
                t0 = time.time()
                out = None
                for _ in range(args.iters):
                    out = f(q)
                host_sync(out)
                dt = (time.time() - t0) / args.iters
            except Exception as e:                   # noqa: BLE001
                print(f"  seq={seq} d={d} {dname} bq={bq_c} bk={bk_c}: "
                      f"FAILED {type(e).__name__}: {e}", flush=True)
                continue
            toks = seq * bh / dt
            print(f"  seq={seq} d={d} {dname} bq={bq_c} bk={bk_c}: "
                  f"{dt * 1e3:.2f} ms  {toks / 1e3:.0f}k tok/s", flush=True)
            if best is None or dt < best[0]:
                best = (dt, bq_c, bk_c)
        if best:
            bucket = 1 << max(0, (seq - 1)).bit_length()
            results[(bucket, d, dname)] = (best[1], best[2])
            print(f"BEST seq={seq} d={d} {dname}: "
                  f"({best[1]}, {best[2]})", flush=True)
    print("\nMEASURED_BLOCKS entries:")
    for k, v in sorted(results.items()):
        print(f"    {k}: {v},")


def decode_sweep(args):
    """Flash-decode (block size, kv-page tile) sweep: B slots decode
    one token each against a pool holding ``span`` resident tokens per
    slot; the timed call is the kernel alone (the engine's scatter
    write and epilogue are tiling-independent)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.pallas import decode as fd
    from paddle_tpu.utils.sync import host_sync

    rng = np.random.RandomState(0)
    B, Hkv, G = args.slots, args.kv_heads, args.q_per_kv
    results = {}
    for span, d, dname in itertools.product(
            (int(s) for s in args.seqs.split(",")),
            (int(s) for s in args.head_dims.split(",")),
            args.dtypes.split(",")):
        dtype = jnp.dtype(dname)
        q = jnp.asarray(rng.randn(B, Hkv, G, d), jnp.float32)
        pos = jnp.full((B,), span - 1, jnp.int32)
        best = None
        for bs in (8, 16, 32, 64, 128, 256):
            if span % bs:
                continue
            P = span // bs
            M = B * span                      # pool at arena parity
            k = jnp.asarray(rng.randn(Hkv, M, d), dtype)   # head-major
            v = jnp.asarray(rng.randn(Hkv, M, d), dtype)
            pages = jnp.asarray(
                rng.permutation(M // bs)[:B * P].reshape(B, P)
                .astype(np.int32))            # scrambled, like production
            for tile in (1, 2, 4, 8):
                if P % tile:
                    continue
                try:
                    f = jax.jit(lambda q_, k_, v_, pg, ps, bs=bs,
                                tile=tile: fd.flash_decode_attention(
                                    q_, k_, v_, pg, ps, block_size=bs,
                                    tile=tile,
                                    interpret=args.interpret))
                    host_sync(f(q, k, v, pages, pos))
                    t0 = time.time()
                    out = None
                    for _ in range(args.iters):
                        out = f(q, k, v, pages, pos)
                    host_sync(out)
                    dt = (time.time() - t0) / args.iters
                except Exception as e:               # noqa: BLE001
                    print(f"  span={span} d={d} {dname} bs={bs} "
                          f"tile={tile}: FAILED "
                          f"{type(e).__name__}: {e}", flush=True)
                    continue
                print(f"  span={span} d={d} {dname} bs={bs} "
                      f"tile={tile}: {dt * 1e6:.0f} us/step "
                      f"({B / dt:.0f} tok/s)", flush=True)
                if best is None or dt < best[0]:
                    best = (dt, bs, tile)
        if best:
            bucket = 1 << max(0, (span - 1)).bit_length()
            results[(fd.POOL_LAYOUT, bucket, d, dname)] = (best[1],
                                                           best[2])
            print(f"BEST span={span} d={d} {dname}: "
                  f"({best[1]}, {best[2]})", flush=True)
    print("\nMEASURED_DECODE entries (layout-keyed):")
    for k, v in sorted(results.items()):
        print(f"    {k}: {v},")


def prefill_sweep(args):
    """Chunked-prefill (chunk, block size, ctx pages-per-tile) sweep:
    one chunk of C tokens attends against ``span`` resident context
    tokens gathered straight off a scrambled pool; the timed call is
    the attention kernel alone (the span-write kernel is
    tiling-independent). ``--dtypes int8,int4`` times the fused-dequant
    gather off quantized pools."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.pallas import prefill as fpf
    from paddle_tpu.utils.sync import host_sync

    rng = np.random.RandomState(0)
    Hkv, G = args.kv_heads, args.q_per_kv
    results = {}
    for span, chunk, d, dname in itertools.product(
            (int(s) for s in args.seqs.split(",")),
            (int(c) for c in args.chunks.split(",")),
            (int(s) for s in args.head_dims.split(",")),
            args.dtypes.split(",")):
        quant = dname in ("int8", "int4")
        dtype = jnp.int8 if quant else jnp.dtype(dname)
        C = chunk
        q = jnp.asarray(rng.randn(C, Hkv, G, d), jnp.float32)
        kck = jnp.asarray(rng.randn(C, Hkv, d), jnp.float32)
        vck = jnp.asarray(rng.randn(C, Hkv, d), jnp.float32)
        best = None
        for bs in (8, 16, 32, 64, 128, 256):
            if span % bs:
                continue
            P_ctx = span // bs
            M = args.slots * span             # pool at arena parity
            d_st = d // 2 if dname == "int4" else d
            if quant:                              # head-major pools
                k = jnp.asarray(rng.randint(-127, 128, (Hkv, M, d_st)),
                                jnp.int8)
                v = jnp.asarray(rng.randint(-127, 128, (Hkv, M, d_st)),
                                jnp.int8)
                ks = jnp.asarray(rng.rand(Hkv, M), jnp.float32)
                vs = jnp.asarray(rng.rand(Hkv, M), jnp.float32)
            else:
                k = jnp.asarray(rng.randn(Hkv, M, d), dtype)
                v = jnp.asarray(rng.randn(Hkv, M, d), dtype)
                ks = vs = None
            pages = jnp.asarray(
                rng.permutation(M // bs)[:P_ctx].astype(np.int32))
            for tile in (1, 2, 4, 8):
                if P_ctx % tile:
                    continue
                try:
                    f = jax.jit(lambda q_, kc, vc, k_, v_, pg, bs=bs,
                                tile=tile, ks=ks, vs=vs:
                                fpf.flash_chunk_prefill(
                                    q_, kc, vc, k_, v_, pg,
                                    block_size=bs, tile=tile,
                                    k_scale=ks, v_scale=vs,
                                    kv_dtype=dname if quant
                                    else "none",
                                    interpret=args.interpret))
                    host_sync(f(q, kck, vck, k, v, pages))
                    t0 = time.time()
                    out = None
                    for _ in range(args.iters):
                        out = f(q, kck, vck, k, v, pages)
                    host_sync(out)
                    dt = (time.time() - t0) / args.iters
                except Exception as e:               # noqa: BLE001
                    print(f"  span={span} C={C} d={d} {dname} bs={bs} "
                          f"tile={tile}: FAILED "
                          f"{type(e).__name__}: {e}", flush=True)
                    continue
                print(f"  span={span} C={C} d={d} {dname} bs={bs} "
                      f"tile={tile}: {dt * 1e6:.0f} us/chunk "
                      f"({C / dt:.0f} tok/s)", flush=True)
                if best is None or dt < best[0]:
                    best = (dt, bs, tile)
        if best:
            sb = 1 << max(0, (span - 1)).bit_length()
            cb = 1 << max(0, (chunk - 1)).bit_length()
            results[(fpf.POOL_LAYOUT, sb, cb, d, dname)] = (best[1],
                                                            best[2])
            print(f"BEST span={span} C={C} d={d} {dname}: "
                  f"({best[1]}, {best[2]})", flush=True)
    print("\nMEASURED_PREFILL entries (layout-keyed):")
    for k_, v_ in sorted(results.items()):
        print(f"    {k_}: {v_},")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seqs", default="1024,2048,4096,8192",
                    help="sequence lengths (attention) / resident "
                         "per-slot spans (--decode)")
    ap.add_argument("--head-dims", default="64,128")
    ap.add_argument("--dtypes", default="bfloat16,float32")
    ap.add_argument("--batch-heads", type=int, default=8)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--decode", action="store_true",
                    help="sweep the flash-decode kernel's (block size, "
                         "kv-page tile) instead of attention blocks")
    ap.add_argument("--prefill", action="store_true",
                    help="sweep the chunked-prefill kernel's (chunk, "
                         "block size, ctx pages-per-tile) instead")
    ap.add_argument("--chunks", default="64,128",
                    help="--prefill: chunk sizes (tokens) to sweep")
    ap.add_argument("--slots", type=int, default=8,
                    help="--decode: concurrent decode slots (B)")
    ap.add_argument("--kv-heads", type=int, default=8,
                    help="--decode: KV heads in the pool")
    ap.add_argument("--q-per-kv", type=int, default=1,
                    help="--decode: query heads per KV head (GQA group)")
    ap.add_argument("--interpret", action="store_true",
                    help="--decode: run the kernel interpreted "
                         "(plumbing check off-TPU; timings meaningless)")
    args = ap.parse_args()
    if args.decode and args.prefill:
        ap.error("--decode and --prefill are separate sweeps")
    if args.decode:
        decode_sweep(args)
    elif args.prefill:
        prefill_sweep(args)
    else:
        attention_sweep(args)


if __name__ == "__main__":
    from paddle_tpu.utils import compile_cache
    compile_cache.configure()
    main()
