#!/usr/bin/env python
"""Seq2seq with attention on WMT14 (reference: demo/seqToseq +
python/paddle/v2/dataset/wmt14.py consumers — encoder-decoder NMT with
the recurrent-group attention decoder).

Run: python demos/seqToseq/train.py [--passes N] [--dict-size V]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import paddle_tpu as paddle
from paddle_tpu.models import seq2seq


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--passes", type=int, default=2)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--dict-size", type=int, default=1000)
    args = ap.parse_args()

    paddle.init(seed=17)
    cost = seq2seq.seq2seq_train(args.dict_size, args.dict_size)
    params = paddle.parameters.create(cost)
    trainer = paddle.trainer.SGD(
        cost=cost, parameters=params,
        update_equation=paddle.optimizer.Adam(
            learning_rate=5e-3, gradient_clipping_threshold=5.0))

    losses = []
    trainer.train(
        reader=paddle.batch(paddle.dataset.wmt14.train(args.dict_size),
                            args.batch_size),
        num_passes=args.passes,
        feeding={"source_language_word": 0, "target_language_word": 1,
                 "target_language_next_word": 2},
        event_handler=lambda e: losses.append(e.cost)
        if isinstance(e, paddle.event.EndIteration) else None)
    print(f"loss {losses[0]:.3f} -> {losses[-1]:.3f}")


if __name__ == "__main__":
    main()
