#!/usr/bin/env python
"""quick_start text classification — the sparse-SEQUENCE configs
(reference: v1_api_demo/quick_start/trainer_config.bow.py /
.emb.py / .cnn.py: sentence sentiment over per-timestep sparse word
vectors, the path that exercised sparse_binary_vector_sequence,
python/paddle/trainer/PyDataProvider2.py:202).

Three selectable pipelines over the imdb reader (synthetic-fallback
aware):
- ``bow``: sparse_binary_vector_sequence → shared fc (sparse weighted
  row-gather) → sequence sum-pool → softmax — the sparse showcase.
- ``emb``: integer_value_sequence → embedding → pool → softmax.
- ``cnn``: embedding → sequence_conv_pool (the .cnn.py topology).

Run: python demos/quick_start/train_text.py [--net bow|emb|cnn]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import paddle_tpu as paddle
from paddle_tpu import layer, networks

VOCAB = 2000


def build(net):
    lbl = layer.data("label", paddle.data_type.integer_value(2))
    if net == "bow":
        # one sparse row per TIMESTEP (word n-hots) — the reference's
        # sparse-sequence data path through the feeder
        words = layer.data(
            "words", paddle.data_type.sparse_binary_vector_sequence(VOCAB))
        h = layer.fc(words, 64, act=paddle.activation.Relu(), name="qs_fc")
        pooled = layer.pool(h, pooling_type=paddle.pooling.Sum())
    elif net == "emb":
        words = layer.data(
            "words", paddle.data_type.integer_value_sequence(VOCAB))
        emb = layer.embedding(words, 64, name="qs_emb")
        pooled = layer.pool(emb, pooling_type=paddle.pooling.Avg())
    else:                                   # cnn
        words = layer.data(
            "words", paddle.data_type.integer_value_sequence(VOCAB))
        emb = layer.embedding(words, 64, name="qs_emb")
        pooled = networks.sequence_conv_pool(
            emb, context_len=3, hidden_size=64, name="qs_cnn")
    out = layer.fc(pooled, 2, act=paddle.activation.Softmax(), name="qs_out")
    return words, layer.classification_cost(out, lbl, name="qs_cost")


def to_sparse_seq(reader):
    """integer_value_sequence sample → per-timestep singleton index
    lists (each word is a 1-hot row; n-gram feeds would emit several
    indices per step)."""
    def gen():
        for words, label in reader():
            yield [[w] for w in words], label
    return gen


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--net", choices=("bow", "emb", "cnn"), default="bow")
    ap.add_argument("--passes", type=int, default=2)
    ap.add_argument("--batch-size", type=int, default=64)
    args = ap.parse_args()

    paddle.init(seed=9)
    word_idx = {f"w{i}": i for i in range(VOCAB - 1)}
    word_idx["<unk>"] = VOCAB - 1
    reader = paddle.dataset.imdb.train(word_idx)
    if args.net == "bow":
        reader = to_sparse_seq(reader)
    _, cost = build(args.net)
    params = paddle.parameters.create(cost)
    trainer = paddle.trainer.SGD(
        cost=cost, parameters=params,
        update_equation=paddle.optimizer.Adam(learning_rate=2e-3))
    losses = []
    trainer.train(
        reader=paddle.batch(paddle.reader.firstn(reader, 1024),
                            args.batch_size),
        num_passes=args.passes,
        event_handler=lambda e: losses.append(e.cost)
        if isinstance(e, paddle.event.EndIteration) else None)
    print(f"net={args.net}: first loss {losses[0]:.4f} -> "
          f"last {losses[-1]:.4f}")
    assert losses[-1] < losses[0]


if __name__ == "__main__":
    main()
