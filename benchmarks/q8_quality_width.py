#!/usr/bin/env python
"""q8/q8sr/defer quality ladder at ImageNet-class channel widths.

The round-4 quality evidence lived on a 16-channel toy net; the claim
that per-channel scales average better at real widths was extrapolation
(VERDICT r4 "Missing #4"). This runs the decision-relevant arms
(unfused / defer / q8sr / q8) on the model_zoo CIFAR ResNet widened to
the 64–256-channel ladder (models/resnet.resnet_cifar10(width=64) —
stage widths 64/128/256, the same span as ResNet-50's 3x3 trunk convs),
≥1k steps, identical init/data order across arms, held-out accuracy
sampled mid-training (where deterministic q8's transient dip lives) and
at the end.

Reference analog: the book-test convergence suite
(/root/reference/python/paddle/v2/framework/tests/book/
test_image_classification_train.py) — train a real topology for real
steps and check the quality metric, not just the loss.

Run: python benchmarks/q8_quality_width.py [--steps 1000] [--width 64]
Artifact: benchmarks/runs/q8_quality_width<W>_s<steps>.json
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=1000)
    ap.add_argument("--width", type=int, default=64)
    ap.add_argument("--depth", type=int, default=20)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--eval-every", type=int, default=200)
    ap.add_argument("--modes", default="0,defer,q8sr,q8")
    ap.add_argument("--noise", type=float, default=3.0,
                    help="sample noise sigma; must be large enough that "
                    "the width-64 net does NOT saturate held-out "
                    "accuracy, or arm differences become invisible")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import layer
    from paddle_tpu.models import resnet
    from paddle_tpu.topology import Topology, Value
    from paddle_tpu.utils.rng import KeySource

    # synthetic CIFAR-shaped task (no dataset egress in this
    # environment): 10 classes of smoothed prototype images + noise at
    # an SNR where a ResNet-20 reaches high-but-not-saturated held-out
    # accuracy within ~1k steps — quality differences stay visible.
    rng = np.random.RandomState(0)
    dim = 3 * 32 * 32
    raw = rng.randn(10, 3, 32, 32).astype(np.float32)
    # smooth spatially so convs have structure to exploit
    protos = raw
    for _ in range(2):
        protos = (protos
                  + np.roll(protos, 1, 2) + np.roll(protos, -1, 2)
                  + np.roll(protos, 1, 3) + np.roll(protos, -1, 3)) / 5.0
    protos = protos.reshape(10, dim)
    protos /= np.abs(protos).max(1, keepdims=True)
    n_train, n_test = 2048, 512

    def make(n, seed):
        r = np.random.RandomState(seed)
        ys = r.randint(0, 10, n)
        xs = (protos[ys]
              + r.randn(n, dim).astype(np.float32) * args.noise)
        return xs.astype(np.float32), ys.astype(np.int32)

    xs, ys = make(n_train, 1)
    xt, yt = make(n_test, 2)

    def held_out_acc(fwd, p, s):
        accs = []
        bs = 128
        for j in range(0, n_test, bs):
            probs, _ = fwd(p, s, {"img": Value(jnp.asarray(xt[j:j + bs])),
                                  "lbl": Value(jnp.asarray(yt[j:j + bs]))},
                           is_training=False)
            accs.append(np.asarray(probs["rc_fc"].array).argmax(-1)
                        == yt[j:j + bs])
        return float(np.concatenate(accs).mean())

    results = {}
    for mode_s in args.modes.split(","):
        mode = {"0": False, "1": True}.get(mode_s, mode_s)
        t0 = time.time()
        img = layer.data("img", paddle.data_type.dense_vector(dim))
        lbl = layer.data("lbl", paddle.data_type.integer_value(10))
        sm = resnet.resnet_cifar10(img, depth=args.depth, class_num=10,
                                   fused_bn=mode, width=args.width)
        cost = layer.classification_cost(sm, lbl, name="w_cost")
        topo = Topology([cost, sm])
        params = paddle.parameters.create(cost, KeySource(7))
        fwd = topo.compile()
        opt = paddle.optimizer.Momentum(momentum=0.9, learning_rate=0.05)
        o = opt.init_state(params.values)

        @jax.jit
        def step(p, o, s, bx, by, key):
            def loss_fn(p):
                outs, ns = fwd(p, s, {"img": Value(bx), "lbl": Value(by)},
                               is_training=True, dropout_key=key)
                return (jnp.mean(outs["w_cost"].array.astype(
                    jnp.float32)), ns)
            (l, ns), g = jax.value_and_grad(loss_fn, has_aux=True)(p)
            np_, no_ = opt.update(jnp.asarray(0, jnp.int32), g, p, o)
            return l, np_, no_, ns

        p, s = params.values, params.state
        bs = args.batch
        losses, curve = [], []
        for i in range(args.steps):
            j = (i * bs) % (n_train - bs + 1)
            l, p, o, s = step(p, o, s, jnp.asarray(xs[j:j + bs]),
                              jnp.asarray(ys[j:j + bs]),
                              jax.random.PRNGKey(1000 + i))
            losses.append(float(l))
            if (i + 1) % args.eval_every == 0:
                acc = held_out_acc(fwd, p, s)
                curve.append({"step": i + 1, "acc": round(acc, 4)})
                print(f"  mode={mode_s:6} step {i+1:5d} "
                      f"loss {losses[-1]:.4f} heldout {acc:.4f}",
                      flush=True)
        results[mode_s] = {
            "final_loss": round(losses[-1], 4),
            "first_loss": round(losses[0], 4),
            "curve": curve,
            "final_acc": curve[-1]["acc"] if curve else None,
            "min_acc_after_first_eval": (min(c["acc"] for c in curve)
                                         if curve else None),
            "wall_s": round(time.time() - t0, 1),
        }
        print(f"mode={mode_s:6} done in {results[mode_s]['wall_s']}s: "
              f"final acc {results[mode_s]['final_acc']}", flush=True)

        # write after EVERY arm so a wall-clock cutoff still leaves the
        # completed arms' evidence on disk
        out = {
            "config": {"width": args.width, "depth": args.depth,
                       "batch": args.batch, "steps": args.steps,
                       "noise": args.noise,
                       "channel_ladder": [args.width, 2 * args.width,
                                          4 * args.width],
                       "task": "synthetic 10-class CIFAR-shaped"},
            "results": results,
        }
        path = os.path.join(
            REPO, "benchmarks", "runs",
            f"q8_quality_width{args.width}_s{args.steps}.json")
        with open(path, "w") as f:
            json.dump(out, f, indent=1)
        print(f"wrote {path} ({len(results)} arm(s))", flush=True)
    if "0" in results and results["0"]["final_acc"] is not None:
        base = results["0"]["final_acc"]
        for m, r in results.items():
            if m == "0":
                continue
            print(f"{m}: final {r['final_acc']:+.4f} vs base {base:.4f} "
                  f"(delta {r['final_acc'] - base:+.4f}); "
                  f"mid-training min {r['min_acc_after_first_eval']:.4f}")


if __name__ == "__main__":
    from paddle_tpu.utils import compile_cache
    compile_cache.configure()
    main()
