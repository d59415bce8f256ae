#!/usr/bin/env python
"""Model-zoo workflows: feature extraction at a named layer + parameter
dump (reference: v1_api_demo/model_zoo/resnet/classify.py extracts
activations of a chosen layer from a trained model;
model_zoo/embedding/extract_para.py dumps an embedding matrix to text).

Loads the checked-in PRETRAINED zoo artifact (demos/model_zoo/
pretrained/resnet_cifar8.tar.gz, held-out accuracy recorded in
PRETRAINED.md — produced by train_pretrained.py; the reference shipped
downloadable trained models the same way), then: (1) re-saves/reloads
through the tar round-trip, (2) runs inference pruned to an
INTERMEDIATE layer (feature extraction — any layer's output is
addressable by name), (3) dumps a parameter matrix to a text file in
the extract_para format (rows of space-separated floats).
``--retrain`` ignores the artifact and trains from scratch instead.

Run: python demos/model_zoo/extract.py [--retrain] [--out-dir DIR]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import paddle_tpu as paddle
from paddle_tpu import layer
from paddle_tpu.models import resnet


def build(recipe=False):
    """recipe: resnet fused_bn mode (False dense; "1"->True streaming-BN;
    "int8"/"full"/"q8"/"defer"/"q8sr") — parameter names interchange
    across modes, so artifacts stay loadable either way."""
    if recipe == "1":
        recipe = True
    img = layer.data("image", paddle.data_type.dense_vector(3 * 32 * 32))
    lbl = layer.data("label", paddle.data_type.integer_value(10))
    out = resnet.resnet_cifar10(img, depth=8, class_num=10,
                                fused_bn=recipe)
    cost = layer.classification_cost(out, lbl, name="cost")
    return img, out, cost


PRETRAINED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "pretrained", "resnet_cifar8.tar.gz")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--retrain", action="store_true",
                    help="train from scratch instead of loading the "
                         "checked-in pretrained artifact")
    ap.add_argument("--passes", type=int, default=1)
    ap.add_argument("--batches", type=int, default=4)
    ap.add_argument("--out-dir", default="/tmp/paddle_tpu_model_zoo")
    args = ap.parse_args()
    os.makedirs(args.out_dir, exist_ok=True)

    paddle.init(seed=5)
    img, out, cost = build()
    if not args.retrain and os.path.exists(PRETRAINED):
        import gzip
        import io
        with gzip.open(PRETRAINED, "rb") as f:
            params = paddle.parameters.Parameters.from_tar(
                io.BytesIO(f.read()))
        print(f"loaded pretrained zoo artifact {PRETRAINED}")
    else:
        params = paddle.parameters.create(cost)
        trainer = paddle.trainer.SGD(
            cost=cost, parameters=params,
            update_equation=paddle.optimizer.Momentum(learning_rate=0.01,
                                                      momentum=0.9))
        reader = paddle.reader.firstn(paddle.dataset.cifar.train10(),
                                      32 * args.batches)
        trainer.train(reader=paddle.batch(reader, 32),
                      num_passes=args.passes)
        params = trainer.parameters

    model_path = os.path.join(args.out_dir, "resnet_cifar.tar")
    with open(model_path, "wb") as f:
        params.to_tar(f)
    print(f"saved {model_path}")

    # (1) reload into a fresh Parameters object
    with open(model_path, "rb") as f:
        loaded = paddle.parameters.Parameters.from_tar(f)

    # (2) feature extraction: prune the program to the global-average-pool
    # layer (the penultimate feature vector, as classify.py's
    # --job=extract does for resnet features)
    from paddle_tpu.topology import Topology
    gap = Topology(cost).find("rc_gap")
    feats = paddle.infer(
        output_layer=gap,
        parameters=loaded,
        input=[(np.random.RandomState(0).rand(3 * 32 * 32)
                .astype(np.float32),)],
        feeding={"image": 0})
    print(f"extracted features: shape {np.asarray(feats).shape}")

    # (3) dump a parameter matrix as text (extract_para.py format)
    wname = sorted(loaded.names())[0]
    mat = loaded[wname]
    txt_path = os.path.join(args.out_dir, f"{wname.replace('/', '_')}.txt")
    with open(txt_path, "w") as f:
        for row in mat.reshape(mat.shape[0], -1):
            f.write(" ".join(f"{x:.6f}" for x in row) + "\n")
    print(f"dumped {wname} {mat.shape} -> {txt_path}")


if __name__ == "__main__":
    main()
