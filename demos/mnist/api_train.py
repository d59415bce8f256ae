#!/usr/bin/env python
"""MNIST LeNet-5 training demo (reference: v1_api_demo/mnist/api_train.py —
the canonical v2-API walkthrough: layers -> trainer.SGD -> events).

Run: python demos/mnist/api_train.py [--passes N] [--batch-size B]
Uses cached real MNIST when present, else the labelled synthetic fallback.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import paddle_tpu as paddle
from paddle_tpu import layer


def lenet5(img):
    """(reference: the api_train.py conv topology)"""
    conv1 = layer.img_conv(img, filter_size=5, num_filters=20,
                           num_channels=1, padding=0,
                           act=paddle.activation.Relu(), name="conv1")
    pool1 = layer.img_pool(conv1, pool_size=2, stride=2, name="pool1")
    conv2 = layer.img_conv(pool1, filter_size=5, num_filters=50, padding=0,
                           act=paddle.activation.Relu(), name="conv2")
    pool2 = layer.img_pool(conv2, pool_size=2, stride=2, name="pool2")
    return layer.fc(pool2, 10, act=paddle.activation.Softmax(), name="fc")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--passes", type=int, default=2)
    ap.add_argument("--batch-size", type=int, default=128)
    ap.add_argument("--checkpoint-dir", default=None)
    args = ap.parse_args()

    paddle.init(seed=42)
    img = paddle.layer.data("pixel", paddle.data_type.dense_vector(784))
    lbl = paddle.layer.data("label", paddle.data_type.integer_value(10))
    out = lenet5(img)
    cost = layer.classification_cost(out, lbl, name="cost")
    err = paddle.evaluator.classification_error(out, lbl, name="err")

    params = paddle.parameters.create(cost)
    trainer = paddle.trainer.SGD(
        cost=cost, parameters=params, extra_layers=[err],
        update_equation=paddle.optimizer.Momentum(
            learning_rate=0.05, momentum=0.9,
            learning_rate_schedule="poly", learning_rate_args="0.001,0.75"))

    def handler(event):
        if isinstance(event, paddle.event.EndPass):
            result = trainer.test(
                reader=paddle.batch(paddle.dataset.mnist.test(), 256))
            print(f"pass complete: test cost {result.cost:.4f} "
                  f"{trainer.evaluators.result()}")

    trainer.train(
        reader=paddle.reader.decorator.shuffle(
            paddle.batch(paddle.dataset.mnist.train(), args.batch_size),
            buf_size=50),
        num_passes=args.passes, event_handler=handler,
        checkpoint_dir=args.checkpoint_dir)

    # inference on a few test images
    import numpy as np
    samples = [s for s, _ in zip(paddle.dataset.mnist.test()(), range(8))]
    probs = paddle.infer(output_layer=out, parameters=params,
                         input=[[s[0]] for s in samples])
    pred = np.argmax(np.asarray(probs), axis=-1)
    print("labels:", [s[1] for s in samples])
    print("preds: ", pred.tolist())


if __name__ == "__main__":
    main()
