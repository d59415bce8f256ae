#!/usr/bin/env python
"""ResNet-50 ImageNet training throughput on one TPU chip.

Mirrors the reference's benchmark protocol (`paddle train --job=time`,
benchmark/paddle/image/run.sh:9-17, resnet.py topology) — measures steady-
state train-step time for ResNet-50 (1000 classes, 3x224x224), reporting
images/sec/chip against the BASELINE.json north star of 4000 images/sec/chip.

A straight-line, single-process program: it asserts a TPU, builds the
step, warms each batch size up (compilation is set-up time, reported
apart), times work that ends in ``jax.block_until_ready`` and prints one
JSON line naming the device it ran on. Anything that goes wrong raises
and the exit code is non-zero; there is no retry, no fallback value and
no assumed peak — ``mfu`` divides by ``core.place.peak_flops`` of the
attached ``device_kind``, and an unknown kind is an error.

Configuration (environment, shared with benchmarks/configs and
benchmarks/profile_step.py): ``BENCH_S2D`` (space-to-depth stem, default
1), ``BENCH_FUSED_BN`` (conv→BN recipe, ``_synth.parse_fused_bn``),
``BENCH_BATCH_SIZES`` (default "128,256"). ``--metrics-out=PATH`` leaves a
JSONL trail (one record per measured batch size plus the result) in the
format ``paddle_tpu stats --metrics_file=...`` tails.
"""

import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmarks"))
sys.path.insert(0, os.path.join(REPO, "benchmarks", "configs"))

from _synth import parse_fused_bn  # noqa: E402
from bench_metrics import metrics_write, resolve_metrics_out  # noqa: E402

NORTH_STAR = 4000.0  # images/sec/chip (BASELINE.json)
# MFU basis: textbook analytic fwd+bwd FLOPs (not XLA's recompute-inflated
# count). BENCHMARKS.md documents the basis.
GFLOP_PER_IMAGE = 12.3


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def stem_s2d_default() -> bool:
    return os.environ.get("BENCH_S2D", "1") == "1"


def resnet50_cost(stem_s2d=None, fused_bn=None):
    """The measured topology: ResNet-50, 1000 classes, 3x224x224,
    softmax cross-entropy. Returns the cost layer (data layers are
    named ``image`` / ``label``). ``chip_smoke.py`` trains the same
    recipe through ``paddle.trainer.SGD``."""
    import paddle_tpu as paddle
    from paddle_tpu import layer
    from paddle_tpu.models import resnet

    img = layer.data("image", paddle.data_type.dense_vector(3 * 224 * 224))
    lbl = layer.data("label", paddle.data_type.integer_value(1000))
    out = resnet.resnet_imagenet(
        img, depth=50, class_num=1000,
        stem_space_to_depth=stem_s2d_default() if stem_s2d is None
        else stem_s2d,
        fused_bn=parse_fused_bn() if fused_bn is None else fused_bn)
    return layer.classification_cost(out, lbl, name="cost")


def build_train_step(stem_s2d=None, fused_bn=None):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.topology import Topology, Value
    from paddle_tpu.utils.rng import KeySource

    cost = resnet50_cost(stem_s2d, fused_bn)
    topo = Topology(cost)
    params = paddle.parameters.create(cost, KeySource(42))
    opt = paddle.optimizer.Momentum(momentum=0.9, learning_rate=0.1)
    opt.bind(topo.param_specs())
    opt_state = opt.init_state(params.values)
    fwd = topo.compile()

    def train_step(p, o, s, images, labels, step):
        def loss_fn(p):
            # per-step key: only consumed by stochastic recipes (q8sr)
            dkey = jax.random.fold_in(jax.random.PRNGKey(7), step)
            outs, ns = fwd(p, s, {"image": Value(images),
                                  "label": Value(labels)},
                           is_training=True, dropout_key=dkey)
            return jnp.mean(outs["cost"].array.astype(jnp.float32)), ns

        (loss, ns), grads = jax.value_and_grad(loss_fn, has_aux=True)(p)
        np_, no_ = opt.update(step, grads, p, o)
        return loss, np_, no_, ns

    return (jax.jit(train_step, donate_argnums=(0, 1, 2)), params, opt_state)


def bench_batch(step_fn, carry, batch, warmup=3, iters=20):
    """(images/sec, compile+warm-up seconds, carry) at one batch size.
    The timed window is ``iters`` serial steps closed by one
    ``block_until_ready`` on the last step's outputs."""
    import jax.numpy as jnp
    from paddle_tpu.utils.sync import host_sync

    rng = np.random.RandomState(0)
    # NHWC device-resident synthetic batch (data pipeline measured separately)
    images = jnp.asarray(rng.rand(batch, 224, 224, 3).astype(np.float32))
    labels = jnp.asarray(rng.randint(0, 1000, batch).astype(np.int32))
    p, o, s = carry

    t_compile = time.time()
    for i in range(warmup):
        loss, p, o, s = step_fn(p, o, s, images, labels,
                                jnp.asarray(i, jnp.int32))
    host_sync(p, loss)
    setup_s = time.time() - t_compile
    log(f"bs={batch}: warmup+compile {setup_s:.1f}s")
    t0 = time.time()
    for i in range(iters):
        loss, p, o, s = step_fn(p, o, s, images, labels,
                                jnp.asarray(i, jnp.int32))
    lossv = host_sync(p, loss)
    dt = (time.time() - t0) / iters
    ips = batch / dt
    if not np.isfinite(lossv):
        raise FloatingPointError(f"bs={batch}: loss {lossv} after "
                                 f"{warmup + iters} steps")
    log(f"bs={batch}: {dt*1e3:.2f} ms/step  {ips:.0f} images/sec  "
        f"loss {lossv:.3f}")
    return ips, setup_s, (p, o, s)


def main(argv=None):
    metrics_out = resolve_metrics_out(argv)
    from paddle_tpu.utils import compile_cache
    compile_cache.configure()
    import jax
    from paddle_tpu.core import place

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench.py measures a TPU; JAX found {device}. A CPU "
            f"timing is not a device metric and none is printed.")
    peak = place.peak_flops(dev, required=True)
    fused_bn = parse_fused_bn()
    stem_s2d = stem_s2d_default()
    step_fn, params, opt_state = build_train_step(stem_s2d, fused_bn)
    carry = (params.values, opt_state, params.state)
    sizes = tuple(int(b) for b in
                  os.environ.get("BENCH_BATCH_SIZES", "128,256").split(","))
    per_batch = {}
    for batch in sizes:
        ips, setup_s, carry = bench_batch(step_fn, carry, batch)
        per_batch[batch] = ips
        metrics_write(metrics_out, kind="bench_batch", batch=batch,
                      images_per_sec=round(ips, 1),
                      setup_s=round(setup_s, 1), mode=str(fused_bn),
                      mfu=round(ips * GFLOP_PER_IMAGE * 1e9 / peak, 4))
    best_batch = max(per_batch, key=per_batch.get)
    value = per_batch[best_batch]
    rec = {"metric": "resnet50_train_images_per_sec_per_chip",
           "value": round(value, 1), "unit": "images/sec",
           "batch": best_batch,
           "images_per_sec_by_batch": {str(b): round(v, 1)
                                       for b, v in per_batch.items()},
           "vs_baseline": round(value / NORTH_STAR, 4),
           "mfu": round(value * GFLOP_PER_IMAGE * 1e9 / peak, 4),
           "peak_tflops": peak / 1e12,
           "stem_space_to_depth": stem_s2d, "fused_bn": fused_bn,
           "device": device, "compile_cache": compile_cache.stats()}
    metrics_write(metrics_out, kind="bench_result", **rec)
    print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
