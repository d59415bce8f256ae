#!/usr/bin/env python
"""Sequence tagging with a linear-chain CRF (reference:
v1_api_demo/sequence_tagging/linear_crf.py — CoNLL-style SRL/NER tagging
with crf_layer cost and crf_decoding at test time).

Run: python demos/sequence_tagging/linear_crf.py [--passes N]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import paddle_tpu as paddle
from paddle_tpu import layer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--passes", type=int, default=3)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--vocab", type=int, default=500)
    ap.add_argument("--tags", type=int, default=7)
    args = ap.parse_args()

    paddle.init(seed=11)
    words = layer.data("words", paddle.data_type.integer_value_sequence(
        args.vocab))
    tags = layer.data("tags", paddle.data_type.integer_value_sequence(
        args.tags))
    emb = layer.embedding(words, 64, name="crf_emb")
    feat = layer.fc(emb, args.tags, act=None, name="crf_feat")
    crf = layer.crf_layer(feat, tags, size=args.tags, name="crf_cost")
    # decoding shares the training CRF's transition matrix by name
    decode = layer.crf_decoding_layer(
        feat, size=args.tags, name="crf_decode",
        param_attr=layer.ParamAttr(name="crf_cost.w"))
    chunk = paddle.evaluator.chunk(decode, tags, num_chunk_types=3,
                                   chunk_scheme="IOB", name="chunk_f1")

    params = paddle.parameters.create(crf)
    trainer = paddle.trainer.SGD(
        cost=crf, parameters=params, extra_layers=[decode, chunk],
        update_equation=paddle.optimizer.Adam(learning_rate=5e-3))

    reader = paddle.dataset.synthetic.sequence_tagging(
        1024, args.vocab, args.tags, seed=5)
    losses = []
    trainer.train(
        reader=paddle.batch(reader, args.batch_size),
        num_passes=args.passes,
        event_handler=lambda e: losses.append(e.cost)
        if isinstance(e, paddle.event.EndIteration) else None)
    print(f"first loss {losses[0]:.3f} -> last {losses[-1]:.3f}  "
          f"{trainer.evaluators.result()}")


if __name__ == "__main__":
    main()
