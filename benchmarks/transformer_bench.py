#!/usr/bin/env python
"""Transformer LM throughput on one chip.

Training mode (default): tokens/sec with and without the Pallas
flash-attention kernel — the modern long-context headline next to the
BASELINE.md image/RNN tables.

Decode mode (--decode): autoregressive serving throughput
(generated tokens/sec through prefill + the compiled single-token scan),
MHA vs GQA (n_kv_heads) — the KV-cache bandwidth lever measured.

Usage: python benchmarks/transformer_bench.py [--seq 2048] [--batch 8]
       python benchmarks/transformer_bench.py --decode [--gen 256]
Prints one JSON line per variant.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--d-model", type=int, default=512)
    ap.add_argument("--layers", type=int, default=6)
    ap.add_argument("--vocab", type=int, default=32000)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--decode", action="store_true",
                    help="serving decode throughput (MHA vs GQA) instead "
                         "of training")
    ap.add_argument("--gen", type=int, default=256,
                    help="tokens to generate per decode measurement")
    ap.add_argument("--flash", choices=("both", "on", "off"),
                    default="both",
                    help="which attention variants to measure")
    ap.add_argument("--weights-int8", action="store_true",
                    help="decode with per-output-channel int8 weights "
                    "(io/lm_serving.quantize_lm_params; dequant fused "
                    "into the matmul operand reads — decode is "
                    "weight-read-bound)")
    ap.add_argument("--remat", choices=("none", "bf16", "q8"),
                    default="none",
                    help="layer-granular recompute with a (quantized) "
                    "stash of each block's input (ops/q8.q8_remat) — "
                    "the long-context capacity lever")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import transformer as tfm

    rng = np.random.RandomState(0)
    if args.weights_int8 and not args.decode:
        ap.error("--weights-int8 only applies to --decode (the training "
                 "path has its own recipes: --remat / BENCH_FUSED_BN)")
    if args.decode:
        _run_decode(args, tfm, jax, jnp, rng)
        return
    tokens = jnp.asarray(rng.randint(0, args.vocab,
                                     (args.batch, args.seq)), jnp.int32)

    variants = {"both": (False, True), "on": (True,),
                "off": (False,)}[args.flash]
    for use_flash in variants:
        try:
            _run_variant(args, tfm, jax, jnp, tokens, use_flash)
        except Exception as e:
            # e.g. plain attention's O(T^2) scores OOM at long seq where
            # the flash variant fits — report and keep going
            msg = str(e).splitlines()[0][:200]
            print(json.dumps({
                "metric": "transformer_lm_tokens_per_sec",
                "flash_attention": use_flash,
                "seq": args.seq, "batch": args.batch,
                "error": f"{type(e).__name__}: {msg}"}), flush=True)


def _run_variant(args, tfm, jax, jnp, tokens, use_flash):
    cfg = tfm.TransformerConfig(
        vocab=args.vocab, d_model=args.d_model, n_layers=args.layers,
        n_heads=args.d_model // 64, d_ff=4 * args.d_model,
        max_len=args.seq, use_flash_attention=use_flash,
        remat=args.remat)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    # the framework optimizer serves the transformer's nested pytree
    # directly via tree_update (same per-array Adam rule as the v2 path)
    from paddle_tpu import optimizer as popt
    adam = popt.Adam(learning_rate=1e-4)
    opt_state = adam.tree_init_state(params)
    targets = jnp.roll(tokens, -1, axis=1)

    def train_step(p, o, toks, tgts, i):
        loss, g = jax.value_and_grad(tfm.lm_loss)(p, toks, tgts, cfg)
        newp, o = adam.tree_update(i, g, p, o)
        return loss, newp, o

    from paddle_tpu.utils.sync import host_sync

    step = jax.jit(train_step, donate_argnums=(0, 1))
    p, o = params, opt_state
    t0 = time.time()
    loss, p, o = step(p, o, tokens, targets, jnp.asarray(0, jnp.int32))
    host_sync(p, loss)
    compile_s = time.time() - t0
    t0 = time.time()
    for i in range(args.iters):
        loss, p, o = step(p, o, tokens, targets,
                          jnp.asarray(i + 1, jnp.int32))
    host_sync(p, loss)
    dt = (time.time() - t0) / args.iters
    toks_per_s = args.batch * args.seq / dt
    print(json.dumps({
        "metric": "transformer_lm_tokens_per_sec",
        "flash_attention": use_flash, "remat": args.remat,
        "seq": args.seq, "batch": args.batch,
        "d_model": args.d_model, "layers": args.layers,
        "ms_per_step": round(dt * 1e3, 2),
        "value": round(toks_per_s, 1),
        "compile_s": round(compile_s, 1),
        "loss": round(float(loss), 4)}), flush=True)
    del p, o, params, opt_state


def _run_decode(args, tfm, jax, jnp, rng):
    """Serving decode: tokens/sec through prefill + the compiled
    single-token scan, MHA vs GQA cache layouts."""
    import time as _t

    from paddle_tpu.utils.sync import host_sync

    heads = args.d_model // 64
    prompt_len = min(64, args.seq)
    for n_kv in (0, max(1, heads // 4)):          # MHA, then GQA H/4
        cfg = tfm.TransformerConfig(
            vocab=args.vocab, d_model=args.d_model, n_layers=args.layers,
            n_heads=heads, n_kv_heads=n_kv, d_ff=4 * args.d_model,
            max_len=prompt_len + args.gen)
        params = tfm.init_params(jax.random.PRNGKey(0), cfg)
        if args.weights_int8:
            # generate() threads {"q8","scale"} weights through the scan
            # carry and dequantizes per step — hoist-proof int8 reads
            from paddle_tpu.io import lm_serving
            params = lm_serving.quantize_lm_params(params)
        gen = jax.jit(lambda p, pr: tfm.generate(
            p, pr, cfg, max_new=args.gen))
        prompt = jnp.asarray(rng.randint(0, args.vocab,
                                         (args.batch, prompt_len)),
                             jnp.int32)
        t0 = _t.time()
        host_sync(gen(params, prompt))
        compile_s = _t.time() - t0
        t0 = _t.time()
        reps = max(1, args.iters // 5)
        out = None
        for _ in range(reps):
            out = gen(params, prompt)
        host_sync(out)
        dt = (_t.time() - t0) / reps
        tps = args.batch * args.gen / dt
        kv_mb = (cfg.n_layers * args.batch * (prompt_len + args.gen)
                 * cfg.kv_heads * cfg.head_dim * 2 * 2) / 2**20
        print(json.dumps({
            "metric": "transformer_decode_tokens_per_sec",
            "weights_int8": args.weights_int8,
            "n_kv_heads": cfg.kv_heads, "n_heads": heads,
            "batch": args.batch, "gen": args.gen,
            "prompt_len": prompt_len, "d_model": args.d_model,
            "layers": args.layers, "kv_cache_mb": round(kv_mb, 1),
            "value": round(tps, 1),
            "ms_per_token": round(dt * 1e3 / args.gen, 3),
            "compile_s": round(compile_s, 1)}), flush=True)


if __name__ == "__main__":
    from paddle_tpu.utils import compile_cache
    compile_cache.configure()
    main()
