#!/usr/bin/env python
"""Training-quality check for every fused conv+BN recipe.

Trains the SAME small ResNet (identical init, identical data order)
under fused_bn modes False / True / "int8" / "q8" / "defer" / "q8sr"
and reports per-mode final train loss and held-out accuracy.
Parity is ASSERTED for every mode except deterministic "q8", whose
straight-through stash noise produces a real held-out gap at horizon
(reported, not asserted — BENCHMARKS.md "Convergence at horizon");
"q8sr" (unbiased stochastic rounding) restores parity and IS asserted.
("full" was retired with the Pallas conv kernels in round 5.)
Runs on CPU or TPU — every mode is XLA-level.

Run: python benchmarks/fused_bn_quality.py [--steps 60]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import layer
    from paddle_tpu.models import resnet
    from paddle_tpu.topology import Topology, Value
    from paddle_tpu.utils.rng import KeySource

    rng = np.random.RandomState(0)
    # synthetic separable 4-class task over 3x16x16 images
    protos = rng.randn(4, 3 * 16 * 16).astype(np.float32)
    n_train, n_test = 512, 256

    def make(n, seed):
        r = np.random.RandomState(seed)
        ys = r.randint(0, 4, n)
        xs = (protos[ys] + r.randn(n, 3 * 16 * 16) * 2.0).astype(
            np.float32)
        return xs, ys.astype(np.int32)

    xs, ys = make(n_train, 1)
    xt, yt = make(n_test, 2)

    results = {}
    for mode in (False, True, "int8", "q8", "defer", "q8sr"):
        x = layer.data("img", paddle.data_type.dense_vector(3 * 16 * 16))
        lbl = layer.data("lbl", paddle.data_type.integer_value(4))
        # the q8 pipeline needs a dense stem before its entry stash (the
        # same structure resnet_imagenet uses), and an exit before pooling
        c1 = resnet.conv_bn_layer(x, 16, 3, 1, 1,
                                  paddle.activation.Relu(), ch_in=3,
                                  name="q_c1",
                                  fused=False if resnet._stash_for(mode) else mode)
        if resnet._stash_for(mode):
            _st, _sr = resnet._stash_for(mode)
            c1 = layer.q8_entry(c1, name="q_entry", stash=_st,
                                stochastic=_sr)
        b1 = resnet.basic_block(c1, 16, 16, 1, name="q_b1", fused=mode)
        if resnet._stash_for(mode):
            b1 = layer.q8_exit(b1, name="q_exit")
        pool = layer.img_pool(b1, pool_size=16, stride=1,
                              pool_type=paddle.pooling.Avg())
        sm = layer.fc(pool, 4, act=paddle.activation.Softmax(),
                      name="q_sm")
        cost = layer.classification_cost(sm, lbl, name="q_cost")
        topo = Topology([cost, sm])       # sm kept as an output for eval
        params = paddle.parameters.create(cost, KeySource(7))
        fwd = topo.compile()
        opt = paddle.optimizer.Momentum(momentum=0.9, learning_rate=0.05)
        o = opt.init_state(params.values)

        @jax.jit
        def step(p, o, s, bx, by, key):
            def loss_fn(p):
                outs, ns = fwd(p, s, {"img": Value(bx), "lbl": Value(by)},
                               is_training=True, dropout_key=key)
                return (jnp.mean(outs["q_cost"].array.astype(
                    jnp.float32)), ns)
            (l, ns), g = jax.value_and_grad(loss_fn, has_aux=True)(p)
            np_, no_ = opt.update(jnp.asarray(0, jnp.int32), g, p, o)
            return l, np_, no_, ns

        p, s = params.values, params.state
        bs = 64
        losses = []
        for i in range(args.steps):
            j = (i * bs) % n_train
            bx = jnp.asarray(xs[j:j + bs])
            by = jnp.asarray(ys[j:j + bs])
            l, p, o, s = step(p, o, s, bx, by,
                              jax.random.PRNGKey(1000 + i))
            losses.append(float(l))
        probs, _ = fwd(p, s, {"img": Value(jnp.asarray(xt)),
                              "lbl": Value(jnp.asarray(yt))},
                      is_training=False)
        acc = float((np.asarray(probs["q_sm"].array).argmax(-1)
                     == yt).mean())
        results[str(mode)] = (losses[0], losses[-1], acc)
        print(f"mode={mode!s:6} first loss {losses[0]:.4f}  "
              f"final loss {losses[-1]:.4f}  test acc {acc:.3f}",
              flush=True)

    base = results["False"]
    for mode, (l0, l1, acc) in results.items():
        if mode in ("False", "q8"):  # q8sr IS parity-asserted
            continue
        assert abs(acc - base[2]) < 0.1, (
            f"mode {mode} accuracy {acc} diverged from unfused {base[2]}")
    # q8 carries straight-through-estimator gradient noise by design;
    # REPORT its gap instead of asserting parity (measured on this toy
    # 16-channel net at 200 steps: ~10 points — small-channel nets
    # amplify int8 noise; defer holds exact parity and is the
    # no-quality-risk throughput arm)
    gap = base[2] - results["q8"][2]
    print(f"q8 accuracy gap vs unfused at {args.steps} steps: {gap:+.3f} "
          f"(q8sr: {base[2] - results['q8sr'][2]:+.3f}, "
          f"defer: {base[2] - results['defer'][2]:+.3f})")
    print("PARITY OK: non-q8 modes converge with the unfused path")


if __name__ == "__main__":
    from paddle_tpu.utils import compile_cache
    compile_cache.configure()
    main()
