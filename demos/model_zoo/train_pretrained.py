#!/usr/bin/env python
"""Produce the checked-in model-zoo artifact (reference slot:
v1_api_demo/model_zoo/resnet/ ships downloadable TRAINED models; this
repo has no network, so the zoo artifact is trained here on the
deterministic synthetic-CIFAR world and committed).

Trains the demo ResNet-8 on paddle.dataset.cifar.train10 (the labelled
synthetic fallback — same distribution every run), evaluates held-out
accuracy on test10, and writes demos/model_zoo/pretrained/
resnet_cifar8.tar.gz plus a provenance note. extract.py loads this
artifact by default, so the extract/infer demo runs against a genuinely
trained model.

Run: python demos/model_zoo/train_pretrained.py [--passes N]
"""

import argparse
import gzip
import io
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import numpy as np

import paddle_tpu as paddle


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--passes", type=int, default=6)
    ap.add_argument("--recipe", default=False,
                    help="fused_bn recipe: 1/int8/full/q8/defer/q8sr "
                    "(default dense)")
    args = ap.parse_args()
    out_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "pretrained")
    os.makedirs(out_dir, exist_ok=True)

    paddle.init(seed=5)
    from extract import build                   # same topology as the demo
    img, out, cost = build(recipe=args.recipe)
    params = paddle.parameters.create(cost)
    trainer = paddle.trainer.SGD(
        cost=cost, parameters=params,
        update_equation=paddle.optimizer.Momentum(
            learning_rate=0.05, momentum=0.9,
            learning_rate_schedule="discexp", learning_rate_args="0.5,400"))
    trainer.train(
        reader=paddle.batch(
            paddle.reader.shuffle(paddle.dataset.cifar.train10(),
                                  buf_size=2048, seed=7), 64),
        num_passes=args.passes)

    # held-out evaluation: the artifact must beat chance by a wide margin
    test = list(paddle.dataset.cifar.test10()())
    xs = np.asarray([t[0] for t in test], np.float32)
    ys = np.asarray([t[1] for t in test], np.int32)
    probs = paddle.infer(output_layer=out, parameters=trainer.parameters,
                         input=[(x,) for x in xs], feeding={"image": 0})
    acc = float((np.asarray(probs).argmax(-1) == ys).mean())
    print(f"held-out accuracy: {acc:.3f} (chance 0.100)")
    assert acc > 0.5, f"artifact not trained enough: acc {acc}"

    buf = io.BytesIO()
    trainer.parameters.to_tar(buf)
    path = os.path.join(out_dir, "resnet_cifar8.tar.gz")
    with gzip.open(path, "wb", compresslevel=9) as f:
        f.write(buf.getvalue())
    with open(os.path.join(out_dir, "PRETRAINED.md"), "w") as f:
        f.write(
            "# Model-zoo artifact: resnet_cifar8.tar.gz\n\n"
            f"ResNet-8 (cifar variant), trained by train_pretrained.py on\n"
            f"the deterministic synthetic-CIFAR world "
            f"(dataset/cifar.py train10 fallback,\n"
            f"seed-stable across machines), {args.passes} passes.\n\n"
            f"Held-out accuracy on test10: **{acc:.3f}** "
            f"(chance 0.100).\n\n"
            "Loaded by default in extract.py — the feature-extraction/\n"
            "parameter-dump demo runs against a genuinely trained model\n"
            "(reference slot: v1_api_demo/model_zoo/resnet pretrained "
            "weights).\n")
    print(f"wrote {path} ({os.path.getsize(path)} bytes)")


if __name__ == "__main__":
    main()
